"""The trainer, as a library: :mod:`~torchx_tpu.train.step` (what a
training step is), :mod:`~torchx_tpu.train.run` (``train()`` and its
stages), :mod:`~torchx_tpu.train.report` (what the job says about itself)
and :mod:`~torchx_tpu.train.data` (the token-memmap input pipeline).
``torchx_tpu.examples.train_llama`` is its argparse entry."""
