"""Pallas TPU kernels for the paper's compute hot-spots.

- lstm_cell.py        fused LSTM cell (MVM_X + MVM_H + gates + elementwise
                      — the paper's per-module datapath as one MXU pass)
- wkv6.py             RWKV6 recurrence chunk (VMEM-resident state)
- flash_attention.py  causal flash attention (prefill shapes)
- ops.py              jitted public wrappers (interpreted on CPU, compiled on TPU)
- ref.py              pure-jnp oracles (the allclose targets)
"""
from repro.kernels.ops import flash_attention_op, lstm_cell_op, wkv6_op
from repro.kernels.ref import ref_attention, ref_lstm_cell, ref_wkv6

__all__ = [
    "flash_attention_op",
    "lstm_cell_op",
    "ref_attention",
    "ref_lstm_cell",
    "ref_wkv6",
    "wkv6_op",
]
