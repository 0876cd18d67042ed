"""``scripts/calibrate_falcon_h1.py`` for the cell whose linear layers keep a Gated DeltaNet state a slot
and no K/V: the same runs, the same fp8 control beside each, and with ``--drop-state`` the same fault (the
rows of the store, here ``pools["gdn"]``, that a prompt's last chunk left are zeroed before the slot's first
decode step reads them: state and convolution tail, all six linear layers). Both must come out not correct.

    chiprun -- python3 scripts/calibrate_qwen3_next.py --workload qwen3-next-serve-decode-long --seeds 1,2,3 --seconds 45
    chiprun -- python3 scripts/calibrate_qwen3_next.py --workload qwen3-next-serve-decode-long --seeds 4 --seconds 45 --control '' --drop-state
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from scripts.calibrate_falcon_h1 import dropping_the_state, main  # noqa: E402, F401

if __name__ == "__main__":
    sys.exit(main())
