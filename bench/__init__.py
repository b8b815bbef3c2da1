"""The chip benchmark of the estimation service (see ``BENCHMARK.json``)."""
