"""Mean rows per stacked launch, over every fleet worker's
micro-batcher: the reader of batch_occupancy.serve."""
from bench.harness import load_reader

read = load_reader("batch_occupancy.serve")
