"""METL's chip benchmark (see ``BENCHMARK.json`` at the repository root)."""
