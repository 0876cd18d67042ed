"""``engine.fetch_return_ms`` in a cell that is judged on its tails: the same reading,
listed apart because there it moves ``tpot_p95_ms`` and not the tokens per second."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "_base", os.path.join(os.path.dirname(os.path.abspath(__file__)), "engine.fetch_return_ms.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)

NAME = "engine.fetch_return_ms.latency"
UNIT = _base.UNIT
LAYER = _base.LAYER
MOVES = "tpot_p95_ms"
SOURCE = _base.SOURCE
read = _base.read
