"""The LIRA chip benchmark's yardstick: corpus, traffic, index cache, reference,
trace reduction and work counts. Entry point: ``bench/run.py``."""
