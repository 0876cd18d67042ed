"""The trainer's token-memmap input pipeline, at the path it had while the
trainer was an example: it lives in :mod:`torchx_tpu.train.data`."""

from torchx_tpu.train.data import TokenDataset, device_batches

# for benchmark/lib/train_cell.py and tests/test_data_integ.py, which read
# the two names here
__all__ = ["TokenDataset", "device_batches"]
