"""Matrix generators, one module each, found by a configuration's
``generator`` key. Each exposes ``generate(cfg, key) -> Triplets``: the
matrix made on the device from a JAX key, returned as host triplets (the
form ``repro.core.convert.to_coo`` takes)."""
