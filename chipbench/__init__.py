"""On-chip benchmark of the gossip trainer (see ``BENCHMARK.json``)."""
