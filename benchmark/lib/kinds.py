"""Find a model kind's two files by the name in a configuration's ``model``.

``kinds/<kind>.py`` is the adapter: the only place that knows the kind's
published keys. It maps them onto the program's config object, lays out the
parameter tree, counts parameters, FLOPs and bytes, says what a training
step's ``aux`` must hold, and names the kind's plain reference,
``reference/<REFERENCE>.py``. Both are found by path, under the benchmark
directory the cell came from and then under the benchmark's own (the test
fixtures keep only cells), the way ``run.py::load_reader`` finds a reader.
Nothing here knows a kind by name: a later PR adds the two files beside its
configuration, and edits none.
"""

from __future__ import annotations

import functools
import importlib.util
import os
from types import ModuleType

from .spec import BENCH_DIR

# what every adapter provides, under these names
ADAPTER = (
    "REFERENCE", "program_config", "weight_shapes", "param_count", "train_flops_per_token",
    "forward_flops_per_token", "kv_bytes_per_token", "decode_step_bytes", "aux_must_be_zero",
)


@functools.lru_cache(maxsize=None)
def _load_file(path: str) -> ModuleType:
    sub, file = path.split(os.sep)[-2:]
    spec = importlib.util.spec_from_file_location(f"benchmark_{sub}_{file[:-3]}".replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _find(sub: str, name: str, bench_dir: str | None) -> ModuleType:
    tried = []
    for root in dict.fromkeys(os.path.abspath(d) for d in (bench_dir, BENCH_DIR) if d):
        path = os.path.join(root, sub, f"{name}.py")
        if os.path.isfile(path):
            return _load_file(path)
        tried.append(path)
    raise ValueError(f"no {sub}/{name}.py: looked for {' and '.join(tried)}")


def load(kind: str, bench_dir: str | None = None) -> ModuleType:
    """The adapter ``kinds/<kind>.py``."""
    module = _find("kinds", kind, bench_dir)
    missing = [n for n in ADAPTER if not hasattr(module, n)]
    if missing:
        raise ValueError(f"{module.__file__} lacks {', '.join(missing)}")
    return module


def of(config: dict) -> ModuleType:
    """The adapter of ``config``'s model kind. ``spec.load_cell`` notes in
    the configuration which benchmark directory it was read from."""
    return load(config["model"], config.get("bench_dir"))


def reference(config: dict) -> ModuleType:
    """The kind's plain reference: ``stream`` and ``head`` (a serving cell's
    check gives the head the served positions alone), ``logits`` (the head over
    the whole stream) and ``mean_nll`` (a training cell)."""
    return _find("reference", of(config).REFERENCE, config.get("bench_dir"))
