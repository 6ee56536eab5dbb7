"""One module per traffic driver kind, found by the `driver` key of a
traffic file (`bench/traffic/<mix>.json`)."""
