"""``benchmark/calibrate.py`` for a cell whose weights leave no room on the chip for two sets of
logits: the same runs, the same numbers, with the reference's logits moved to the host before
the control's are computed.

``benchmark/lib/serve_cell.py::served_gaps`` holds the reference's ``[tokens, vocab]`` float32
logits on the device while the fp8 control computes its own; beside 11.3 GB of weights and a
131,072-wide head (``xing4-serve-decode-long``: 1.7 GB a set for a 3,300-token request) the chip
runs out of memory (my chip run, PR 33). The benchmark's own runs never run the control and are
not touched; this is how PR 33 read its control (PERF.md section 6, 7.8).

    chiprun -- python3 scripts/calibrate_on_host.py --workload <cell> --seeds 1,2,3 --seconds 45
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def served_gaps(params, config: dict, sample: list[dict], quant=None):  # noqa: ANN001, ANN201
    """``serve_cell.served_gaps``, one set of logits on the device at a time."""
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import kinds

    ref = kinds.reference(config)
    gaps = []
    for s in sample:
        seq = s["prompt"] + s["generated"]
        n_p, n_g = len(s["prompt"]), len(s["generated"])
        padded = -(-len(seq) // 128) * 128
        toks = jnp.asarray([seq + [0] * (padded - len(seq))], jnp.int32)
        lg = np.asarray(ref.logits(params, toks, config)[0, n_p - 1 : n_p - 1 + n_g])
        if quant is None:
            served = np.asarray(s["generated"], np.int64)
        else:
            served = np.asarray(jnp.argmax(ref.logits(params, toks, config, quant)[0, n_p - 1 : n_p - 1 + n_g], axis=-1))
        gaps.extend((lg.max(axis=-1) - lg[np.arange(n_g), served]).tolist())
    return gaps


def main() -> int:
    from benchmark import calibrate
    from benchmark.lib import serve_cell

    serve_cell.served_gaps = served_gaps
    return calibrate.main()


if __name__ == "__main__":
    sys.exit(main())
