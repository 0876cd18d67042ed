"""Bytes of recurrent state a slot holds whatever its length, as the engine laid its store
out (``ServeEngine.state_bytes_per_slot``, carried by every traced ``serve.decode`` span):
over all layers a state ``[H, P, N]`` in float32 and the convolution's last inputs in the
model's type; 25,350,144 at Falcon-H1-34B's widths and 6 layers. A tripwire on the state's
type and layout: a state kept in bfloat16 halves it, and the check's limits were read with
float32. None for an engine that keeps no such state (the attribute is then not there)."""

NAME = "engine.state_bytes_per_slot"
UNIT = "B/slot"
LAYER = "serving engine"
MOVES = "serve_tokens_per_s"
SOURCE = "program_span"


def read(run: dict):
    from benchmark.lib import host_spans, scopes

    hot = scopes.names()
    r = host_spans.of_run(run) if hot is not None else None
    held = [int(s.attrs["state_bytes_per_slot"]) for s in r.named(hot.SERVE_DECODE) if "state_bytes_per_slot" in s.attrs] if r else []
    return float(held[-1]) if held else None
