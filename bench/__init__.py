"""On-chip benchmark of the CNN equalizer (see BENCHMARK.json and PERF.md).

Everything that defines the yardstick lives here, where a change to the
program cannot move it: traffic generation, the plain reference, the
table of peaks, the work counts and the trace reduction. From the program
the benchmark takes only the system under test and its counters.
"""
