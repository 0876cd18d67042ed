"""Share of the training step's device time (``jit_step``) under the scopes ``grad_clip``
and ``optimizer``: the norm of the gradients, AdamW and the weight update."""

NAME = "kernels.train_optimizer_pct"
UNIT = "%"
LAYER = "training kernels"
MOVES = "train_tokens_per_s_per_chip"
SOURCE = "device_trace"
MODULE = "jit_step"


def read(run: dict):
    from benchmark.lib import scopes

    hot = scopes.names()
    if hot is None or run["cell"].kind != "train":
        return None
    return scopes.share_pct(run, MODULE, (hot.GRAD_CLIP, hot.OPTIMIZER))
