"""Work counts of a VGG-style CNN training step, from its shapes.

Each convolution of a step is counted as the direct algorithm does it,
whatever implements it (a Winograd or FFT kernel does not read as less
work): ``2 N K C H W kh kw`` operations for the forward pass, the same for
the input gradient (dIn) and for the kernel gradient (dKer).  The first
layer's input gradient is not needed and is not counted.  The least bytes
of a call are its operands and its result, each read or written once.
"""

from __future__ import annotations

_ITEMSIZE = {"float32": 4, "bfloat16": 2}


def conv_layers(cfg: dict, batch: int):
    """``(N, C, H, W, K, kh, kw)`` of every convolution, in order."""
    h = cfg["image_size"]
    c = cfg["in_channels"]
    k = cfg["kernel_size"]
    out = []
    for i, ko in enumerate(cfg["channels"]):
        out.append((batch, c, h, h, ko, k, k))
        c = ko
        if (i + 1) % cfg["pool_every"] == 0:
            h //= 2
    return out


def conv_calls(cfg: dict, batch: int):
    """``(kind, flops, least bytes)`` of every convolution in one step:
    forward, dIn and dKer of each layer."""
    isz = _ITEMSIZE[cfg["dtype"]]
    calls = []
    for i, (n, c, h, w, k, kh, kw) in enumerate(conv_layers(cfg, batch)):
        flops = 2 * n * k * c * h * w * kh * kw
        x, wt, y = n * c * h * w, k * c * kh * kw, n * k * h * w
        calls.append(("fwd", flops, (x + wt + y) * isz))
        if i > 0:
            calls.append(("dIn", flops, (y + wt + x) * isz))
        calls.append(("dKer", flops, (x + y + wt) * isz))
    return calls


def head_flops(cfg: dict, batch: int) -> int:
    """Forward, dX and dW of the classifier matmul."""
    return 3 * 2 * batch * cfg["channels"][-1] * cfg["num_classes"]


def forward_flops(cfg: dict, batch: int) -> int:
    return (sum(f for kind, f, _ in conv_calls(cfg, batch) if kind == "fwd")
            + 2 * batch * cfg["channels"][-1] * cfg["num_classes"])


def step_flops(cfg: dict, batch: int) -> int:
    """Model operations of one training step (no recomputation counted)."""
    return sum(f for _, f, _ in conv_calls(cfg, batch)) + head_flops(cfg,
                                                                     batch)


def conv_least_time_s(cfg: dict, batch: int, peak_flops: float,
                      hbm_bytes_per_s: float) -> float:
    """Least time of one step's convolutions: each call bounded by the
    larger of its operations over the peak and its bytes over HBM."""
    return sum(max(f / peak_flops, b / hbm_bytes_per_s)
               for _, f, b in conv_calls(cfg, batch))
