"""``benchmark/calibrate.py`` for a cell whose model keeps recurrent state: the same runs and
numbers, and with ``--drop-state`` the run that ISSUE 41 asks the check to catch: the rows of
the mixer's store that a prompt's last chunk left are zeroed before the slot's first decode
step reads them (state and convolution tail, every layer), everything else as the engine does
it. A check that lets that run through does not see the mixer's state at all.

    chiprun -- python3 scripts/calibrate_falcon_h1.py --workload falcon-h1-serve-decode-long --seeds 1,2,3 --seconds 45
    chiprun -- python3 scripts/calibrate_falcon_h1.py --workload falcon-h1-serve-decode-long --seeds 4 --seconds 45 --control '' --drop-state

``scripts/calibrate_qwen3_next.py`` is this script under the name of the other kind that keeps
such state (``StateCache.store`` says where a kind's rows lie). The zeroing is one small compiled program with the store donated; the warm-up's requests
run it first, so it compiles outside the window like the engine's own two.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from torchx_tpu.serve.engine import ServeEngine  # noqa: E402


def dropping_the_state():  # noqa: ANN201
    """``ServeEngine._chunk_enqueued`` with the fault: behind a prompt's last chunk the slot's
    rows of the store are zeroed."""
    import jax
    import jax.numpy as jnp

    enqueued = ServeEngine._chunk_enqueued
    donate = (0,) if jax.default_backend() != "cpu" else ()
    zero_row = jax.jit(lambda store, row: jax.tree.map(lambda p: p.at[:, row].set(0), store), donate_argnums=donate)

    def faulty(self, slot, st, n):  # noqa: ANN001, ANN202
        last = enqueued(self, slot, st, n)
        if last:
            pools, store = self.cache.pools, self.cache.store  # "ssm", or "gdn" where the state is the linear layers'
            self.cache.pools = {**pools, store: zero_row(pools[store], jnp.int32(slot + 1))}
        return last

    return faulty


def main() -> int:
    from benchmark import calibrate

    if "--drop-state" in sys.argv:
        sys.argv.remove("--drop-state")
        ServeEngine._chunk_enqueued = dropping_the_state()
        print("calibrate: the mixer's state is dropped between a prompt's last chunk and its slot's first decode step", flush=True)
    return calibrate.main()


if __name__ == "__main__":
    sys.exit(main())
