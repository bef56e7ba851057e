#!/usr/bin/env python3
"""The control of the check that decides ``correct``.

    python3 bench/control.py --workload kron21.clients32 --seeds 1 2 3 \
        --seconds 5

Runs the cell as ``bench/run.py`` does, once per seed in one process, with
the reference one precision step down in the program's place: the
operator that ``SparseOperator.from_coo`` would build is replaced by one
whose every flush is answered by ``bench.reference.control_multiply``
(bfloat16 values and vectors, float32 arithmetic) on the generator's own
triplets. Each run's line is printed, then one summary line; the command
exits 0 only when every run comes out not ``correct``. The limit is set
between these readings and the largest that sound runs of the program give.
The benchmark's own runs never run this.
"""
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [ROOT, os.path.join(ROOT, "src")] + [
    p for p in sys.path if os.path.abspath(p or ".") != HERE]

import numpy as np  # noqa: E402

from bench import harness, reference, spec  # noqa: E402


class _Control:
    """The control as an operator: what ``SparseOperator`` offers the
    harness, answered by the reference one precision step down."""

    def __init__(self, a):
        self._a = a
        self.shape = a.shape
        self.plan = types.SimpleNamespace(matrix=(), impl="control",
                                          label="control")

    def matmul(self, X):
        import jax.numpy as jnp
        return jnp.asarray(reference.control_multiply(self._a,
                                                      np.asarray(X)))


def install(setattr_=setattr) -> None:
    """Put the control in the program's place: the generator's triplets
    are kept as the control's matrix, and ``SparseOperator.from_coo``
    returns the control, which multiplies by it on the host, in place of
    the program's plan. Tests pass ``monkeypatch.setattr``."""
    from repro.spmm import SparseOperator
    held = {}
    real = spec.generator

    def generator(name, root=spec.ROOT):
        gen = real(name, root)

        def generate(cfg, key):
            trip = gen.generate(cfg, key)
            held["a"] = reference.control_csr(trip.rows, trip.cols,
                                              trip.vals, trip.shape)
            return trip
        return types.SimpleNamespace(generate=generate)

    setattr_(spec, "generator", generator)
    setattr_(SparseOperator, "from_coo",
             classmethod(lambda cls, coo, plan=None, **kw: _Control(
                 held["a"])))
    setattr_(harness, "check_kernel", lambda *a: None)


def main(argv=None) -> int:
    import argparse
    import json
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    install()
    errs, correct = [], []
    for seed in args.seeds:
        out = harness.run_cell(args.workload, seed, args.seconds, False)
        print(json.dumps(out), flush=True)
        errs.append(out["checks"]["normwise_err"]["value"])
        correct.append(out["correct"])
    limit = out["checks"]["normwise_err"]["limit"]
    print(json.dumps({"workload": args.workload, "seeds": args.seeds,
                      "control_normwise_err": errs, "limit": limit,
                      "correct": correct}), flush=True)
    return 0 if not any(correct) else 1


if __name__ == "__main__":
    sys.exit(main())
