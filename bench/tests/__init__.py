"""CPU tests of the benchmark. Run them by path:
``python -m pytest bench/tests``."""
