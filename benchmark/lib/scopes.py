"""Device time of a compiled program by the ``jax.named_scope`` of its operations.

The scope path of an operation (``jit(_decode)/while/body/attn/paged_attention/
scores/dot_general``) is the ``tf_op`` stat of its **event metadata** in the
``.xplane.pb``. ``jax.profiler.ProfileData`` does not show metadata stats, so
this module reads the few message types it needs from the protobuf wire format
itself (XSpace, XPlane, XLine, XEvent, XEventMetadata, XStat, XStatMetadata:
``tsl/profiler/protobuf/xplane.proto``); importing TensorFlow's generated
classes costs half a minute and a dependency.

An operation belongs to the program whose ``XLA Modules`` event contains its
start. Containers (``while``, ``conditional``, ``call``) are left out, as in
``lib/trace.py``: their bodies' operations are listed too. A scope matches a
path component with or without the wrappers autodiff puts round it
(``jvp(attn)``, ``transpose(jvp(attn))``); ``rematted_computation`` in a path
marks recomputation. A fusion carries the path of one of its operations.
"""

from __future__ import annotations

import bisect
import functools
import os
import re
from typing import Iterable, Iterator, Optional

from . import spec
from .trace import CONTAINER_OP, MODULE_LINE, OPS_LINE, find_xplane, module_name

WRAPPER = re.compile(r"^[A-Za-z_]+\((.*)\)$")
REMAT = "rematted_computation"


def names():  # noqa: ANN201
    """The program's span and scope names (``torchx_tpu/obs/hot.py``), None for
    a program built before it had them."""
    try:
        from torchx_tpu.obs import hot
    except ImportError:
        return None
    return hot


def trace_file(run: dict) -> Optional[str]:
    """The ``.xplane.pb`` the harness wrote for this run, None where it traced
    nothing or no operation ran on a device."""
    if not run.get("trace"):
        return None
    try:
        return find_xplane(os.path.join(spec.scratch_dir(run["cell"]), "trace"))
    except FileNotFoundError:
        return None


# -- protobuf wire format ------------------------------------------------------


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    result = shift = 0
    while True:
        byte = buf[i]
        i += 1
        result |= (byte & 0x7F) << shift
        if byte < 0x80:
            return result, i
        shift += 7


def _fields(buf: memoryview) -> Iterator[tuple[int, int, object]]:
    """(field number, wire type, value) of one message: an int for a varint,
    a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i : i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i : i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield tag >> 3, wire, value


def _signed(v: int) -> int:
    return v - (1 << 64) if v >> 63 else v


def _text(v: object) -> str:
    return bytes(v).decode("utf-8", "replace")


def _map_entries(buf: memoryview) -> tuple[int, Optional[memoryview]]:
    key, value = 0, None
    for f, _, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane(buf: memoryview) -> Optional[dict]:
    """A device plane's programs and operations, None for any other plane."""
    name, lines, event_meta, stat_meta = "", [], [], []
    for f, _, v in _fields(buf):
        if f == 2:
            name = _text(v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            event_meta.append(v)
        elif f == 5:
            stat_meta.append(v)
    if not name.startswith("/device:TPU:"):
        return None
    stat_names = {}
    for entry in stat_meta:
        key, body = _map_entries(entry)
        for f, _, v in _fields(body):
            if f == 2:
                stat_names[key] = _text(v)
    tf_op_ids = {k for k, v in stat_names.items() if v == "tf_op"}
    meta: dict[int, tuple[str, str]] = {}  # id -> (HLO text, scope path)
    for entry in event_meta:
        key, body = _map_entries(entry)
        hlo, path = "", ""
        for f, _, v in _fields(body):
            if f == 2:
                hlo = _text(v)
            elif f == 5:  # XStat
                stat = dict((sf, sv) for sf, _, sv in _fields(v))
                if stat.get(1) in tf_op_ids:
                    if 5 in stat:
                        path = _text(stat[5])
                    elif 7 in stat:  # a reference to a stat metadata's name
                        path = stat_names.get(stat[7], "")
        meta[key] = (hlo, path.rstrip(":"))
    out = {"name": name, "modules": [], "ops": []}
    for line in lines:
        line_name, t0_ns, events = "", 0, []
        for f, _, v in _fields(line):
            if f == 2:
                line_name = _text(v)
            elif f == 3:
                t0_ns = _signed(v)
            elif f == 4:
                events.append(v)
        key = {MODULE_LINE: "modules", OPS_LINE: "ops"}.get(line_name)
        if key is None:
            continue
        for ev in events:
            mid = offset_ps = duration_ps = 0
            for f, _, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    offset_ps = _signed(v)
                elif f == 3:
                    duration_ps = _signed(v)
            start = t0_ns * 1e-9 + offset_ps * 1e-12
            hlo, path = meta.get(mid, ("", ""))
            out[key].append((hlo, start, start + duration_ps * 1e-12, path))
    return out


def read_planes(path: str) -> list[dict]:
    """Each device plane as ``{"name", "modules", "ops"}``; an entry of either
    list is ``(HLO text, start_s, end_s, scope path)``."""
    return list(_read_planes(path, os.path.getmtime(path)))


@functools.lru_cache(maxsize=2)
def _read_planes(path: str, _mtime: float) -> tuple[dict, ...]:
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    planes = []
    for f, _, v in _fields(buf):
        if f == 1:
            plane = _plane(v)
            if plane is not None:
                planes.append(plane)
    return tuple(planes)


# -- reduction -------------------------------------------------------------------


def components(path: str) -> list[str]:
    """The path's components with autodiff's wrappers taken off:
    ``jit(step)/transpose(jvp(attn))/dot_general`` -> ``[step, attn, dot_general]``."""
    out = []
    for part in path.split("/"):
        while (m := WRAPPER.match(part)) is not None:
            part = m.group(1)
        if part:
            out.append(part)
    return out


def program_ops(planes: Iterable[dict], module: str) -> Optional[list[tuple[float, list[str], str]]]:
    """``module``'s operations over all its runs, containers left out, as
    ``(device seconds, path components, instruction name)``. None where the
    trace holds no run of ``module``."""
    ops = []
    for p in planes:
        runs = sorted((s, e) for name, s, e, _ in p["modules"] if module_name(name) == module)
        starts = [s for s, _ in runs]
        for hlo, s, e, path in p["ops"] if runs else ():
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= runs[i][1]:
                continue  # another program's operation
            short = hlo.split(" = ")[0].lstrip("%")
            if not CONTAINER_OP.match(short):
                ops.append((e - s, components(path), short))
    return ops or None


def under(ops: list[tuple[float, list[str], str]], scopes: Iterable[str], also: str = "") -> float:
    """Seconds of ``ops`` under any of ``scopes`` (an operation under two of
    them counts once); with ``also``, only those whose path has that
    component too (``REMAT``)."""
    wanted = set(scopes)
    return sum(d for d, parts, _ in ops if wanted.intersection(parts) and (not also or also in parts))


def breakdown(ops: list[tuple[float, list[str], str]], scopes: Iterable[str]) -> dict:
    """``{"total", "scoped", "by_scope": {scope: s}, "unscoped": {last path
    component, or the instruction's name without its number: s}}``, largest
    first. Nested scopes each count their operations."""
    wanted = tuple(scopes)
    unscoped: dict[str, float] = {}
    for d, parts, short in ops:
        if not set(wanted).intersection(parts):
            key = parts[-1] if parts else re.sub(r"\.\d+$", "", short)
            unscoped[key] = unscoped.get(key, 0.0) + d
    rank = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))  # noqa: E731
    return {
        "total": sum(d for d, _, _ in ops),
        "scoped": under(ops, wanted),
        "by_scope": rank({sc: under(ops, (sc,)) for sc in wanted}),
        "unscoped": rank(unscoped),
    }


def share_pct(run: dict, module: str, scopes: Iterable[str]) -> Optional[float]:
    """What a ``kernels.*_pct`` reader returns: the share of ``module``'s
    device time under any of ``scopes``, in percent. None without a trace, and
    where no operation of the program carries any scope the program defines
    (one built before the scopes existed)."""
    hot, path = names(), trace_file(run)
    if hot is None or path is None:
        return None
    ops = program_ops(read_planes(path), module)
    if ops is None or under(ops, hot.DEVICE_SCOPES) <= 0.0:
        return None
    return 100.0 * under(ops, scopes) / sum(d for d, _, _ in ops)
