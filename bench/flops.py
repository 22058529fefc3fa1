"""The useful work of served requests, from the configuration's widths and
the requests' true lengths only.

A row-timestep is one sample of one stream or window through every layer.
Padding rows, padded timesteps, bucket lanes, the schedule and the number
of programs that ran are not counted, so every implementation is held to
the same count and no share of a peak can pass 100% by counting work that
was not asked for.

FLOPs per row-timestep: each layer does two matrix-vector products into
its four gates, ``2 * 4H * (In + H)`` (a multiply and an add per weight);
the elementwise gate arithmetic and the error are left out, as is usual
for a model-FLOPs count.

Bytes per row-timestep, the least any implementation must move through
HBM when the weights stay on chip:

* a window (``score``): its input sample in (``4F``); state stays on chip
  across the window, and the score out is once per window (``4``);
* a stream step (``step``): its sample in (``4F``), every layer's
  ``(h, c)`` read and written back (``4 * 2H * 2`` per layer), and the
  running error read, updated and written (``12``).
"""
from __future__ import annotations


def widths(features: int, depth: int) -> list:
    """Hidden size of each layer of the paper's LSTM-AE: F/2, F/4, ... down
    to the bottleneck, then back up, the last layer F wide."""
    half = depth // 2
    enc = [features // 2 ** (i + 1) for i in range(half)]
    return enc + list(reversed(enc[:-1])) + [features]


def layer_shapes(features: int, depth: int) -> list:
    """(input size, hidden size) of each layer."""
    hs = widths(features, depth)
    return list(zip([features] + hs[:-1], hs))


def flops_per_row_timestep(features: int, depth: int) -> int:
    return sum(2 * 4 * h * (n_in + h) for n_in, h in layer_shapes(features, depth))


def bytes_per_row_timestep(features: int, depth: int, kind: str) -> int:
    if kind == "score":
        return 4 * features
    if kind == "step":
        state = sum(4 * 2 * h * 2 for _, h in layer_shapes(features, depth))
        return 4 * features + state + 12
    raise ValueError(f"unknown kind {kind!r}")


def useful_work(features: int, depth: int, kind: str,
                row_timesteps: int, requests: int) -> tuple:
    """-> (flops, bytes) of ``row_timesteps`` useful row-timesteps served as
    ``requests`` requests (a window's score adds 4 bytes out)."""
    flops = row_timesteps * flops_per_row_timestep(features, depth)
    nbytes = row_timesteps * bytes_per_row_timestep(features, depth, kind)
    if kind == "score":
        nbytes += 4 * requests
    return flops, nbytes
