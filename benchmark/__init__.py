"""The chip benchmark of this repository (see ``BENCHMARK.json``)."""
