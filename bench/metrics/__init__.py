"""One reader per metric, named as the metric is in ``BENCHMARK.json``.
``read(ctx)`` takes a :class:`bench.harness.Context` and returns the
value, or ``None`` where it finds nothing to read: the harness then
leaves the metric out of the result line."""
