from repro.data.lm import LMDataConfig, LMIterator, host_slice, make_lm_batch
from repro.data.timeseries import (
    TimeseriesConfig,
    TimeseriesIterator,
    make_batch,
    make_batch_np,
)

__all__ = [
    "LMDataConfig",
    "LMIterator",
    "TimeseriesConfig",
    "TimeseriesIterator",
    "host_slice",
    "make_batch",
    "make_batch_np",
    "make_lm_batch",
]
