"""The repository's benchmark (BENCHMARK.json): one command runs one
cell. Everything a later PR may not change lives here."""
