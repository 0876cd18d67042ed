"""``benchmark/calibrate.py`` for a cell whose cache is a window's rows and the pooled rows
behind it (EVA attention): the same runs and numbers, and with ``--drop-pooled`` the run that
ISSUE 44 asks the check to catch: the program without ``R_t``. Every window starts from
nothing: a byte's cache coordinate is its place in its window, a slot's table is its current
window alone, and the pooled rows of the windows behind it are computed, staged and kept as
ever but never read. A check that lets that run through does not see the pooled rows at all.

    chiprun -- python3 scripts/calibrate_evabyte.py --workload evabyte-serve-decode-long --seeds 1,2,3 --seconds 45
    chiprun -- python3 scripts/calibrate_evabyte.py --workload evabyte-serve-decode-long --seeds 4 --seconds 45 --control '' --drop-pooled

Nothing is compiled that the engine would not compile: the fault is in the coordinate the
programs compute and in how the host lays a table.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def drop_the_pooled_rows() -> None:
    """The cache coordinate without the pooled rows in front of it, on the device
    (``eva.cache_coord``) and on the host (``EvaTables``)."""
    from torchx_tpu.models import eva
    from torchx_tpu.ops.paged_attention import TRASH_BLOCK
    from torchx_tpu.serve.kv_pool import EvaTables

    lay = EvaTables._lay

    def lay_the_window_alone(self, slot: int) -> None:  # noqa: ANN001
        lay(self, slot)
        window = self._window[slot]
        self.tables[slot, : len(window)] = window
        self.tables[slot, len(window) :] = TRASH_BLOCK

    eva.cache_coord = lambda cfg, t: t % cfg.eva_window
    EvaTables.coord = lambda self, position: position % self.window
    EvaTables._lay = lay_the_window_alone


def main() -> int:
    from benchmark import calibrate

    if "--drop-pooled" in sys.argv:
        sys.argv.remove("--drop-pooled")
        drop_the_pooled_rows()
        print("calibrate: the pooled rows are dropped: every window starts from nothing", flush=True)
    return calibrate.main()


if __name__ == "__main__":
    sys.exit(main())
