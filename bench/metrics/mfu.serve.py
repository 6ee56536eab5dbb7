"""The whole window's share of the chips' peak: useful operations per
second (symbols/s × operations per symbol) over the datapath's peak of
every chip used."""
from bench.readers import mfu_pct as read  # noqa: F401
