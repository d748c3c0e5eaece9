"""Operations and bytes the FF-MLP algorithm requires, counted from
shapes.

These count the work the algorithm needs, not what a kernel happens to
do: the backward of an FF layer needs the weight and bias gradients
only (the layer's input is data, so no input gradient), and padding a
kernel adds to its time but not to the work required of it. All
arrays are float32 (4 bytes).
"""
from __future__ import annotations

import dataclasses
from typing import List

F32 = 4


@dataclasses.dataclass(frozen=True)
class Cost:
    flops: float
    bytes: float

    def least_seconds(self, peak) -> float:
        """The least time the chip could take: the larger of the
        compute bound and the memory bound."""
        return max(self.flops / peak.flops_per_s,
                   self.bytes / peak.hbm_bytes_per_s)


def ff_dense_fwd(M, K, N) -> Cost:
    """y = relu(x @ w + b), g = sum(y^2): x, w, b read, y and g written
    (the same with the norm epilogue, which divides y in place)."""
    return Cost(2.0 * M * K * N, F32 * (M * K + K * N + N + M * N + M))


def ff_dense_bwd(M, K, N) -> Cost:
    """dW = x^T dy and db = sum(dy) from the cotangents: x, y, dL/dy and
    dL/dg read, dW and db written. No dx, and no read of w."""
    return Cost(2.0 * M * K * N, F32 * (M * K + 2 * M * N + M + K * N + N))


@dataclasses.dataclass(frozen=True)
class KernelCalls:
    kernel: str                 # "ff_dense" | "ff_dense_bwd"
    M: int
    K: int
    N: int
    count: int
    what: str

    @property
    def cost(self) -> Cost:
        fn = ff_dense_fwd if self.kernel == "ff_dense" else ff_dense_bwd
        return fn(self.M, self.K, self.N)


def _chunks(n, chunk):
    """Row counts of the program's chunked scoring loop."""
    return [min(chunk, n - i) for i in range(0, n, chunk)]


def job_kernel_calls(model: dict, traffic: dict) -> List[KernelCalls]:
    """Every ``ff_dense`` forward and backward call one training job
    makes, with its shape: the layer steps of every chapter (a stacked
    [pos; neg] batch), the hand-off forwards of both streams between
    layers, the AdaptiveNEG scoring of the whole train set under every
    label after every chapter, and the final evaluation (the test set,
    and on the sequential backend 2,000 train rows, under every label).
    """
    sizes = model["layer_sizes"]
    L = len(sizes) - 1
    C = model["num_classes"]
    B = model["batch_size"]
    S = model["splits"]
    n, n_test = traffic["n_train"], traffic["n_test"]
    steps = -(-n // B) * max(model["epochs"] // S, 1)   # per layer-chapter
    out = []
    for k in range(L):
        K, N = sizes[k], sizes[k + 1]
        out.append(KernelCalls("ff_dense", 2 * B, K, N, S * steps,
                               f"train step, layer {k}"))
        out.append(KernelCalls("ff_dense_bwd", 2 * B, K, N, S * steps,
                               f"train step, layer {k}"))
        if k + 1 < L:
            out.append(KernelCalls("ff_dense", n, K, N, S * 2,
                                   f"hand-off forward, layer {k}"))
    chunks = []
    if model["neg_mode"] == "adaptive":
        chunks += [(m, S, "AdaptiveNEG scoring") for m in _chunks(n, 2000)]
    eval_rows = _chunks(n_test, 2000)
    if traffic["backend"] == "sequential":
        eval_rows += _chunks(min(n, 2000), 2000)
    chunks += [(m, 1, "evaluation") for m in eval_rows]
    for m, times, what in chunks:
        for k in range(L):
            out.append(KernelCalls("ff_dense", m * C, sizes[k], sizes[k + 1],
                                   times, what))
    return out


def job_model_flops(model: dict, traffic: dict) -> float:
    """Model FLOPs of one job: every forward product and weight
    gradient above. Excludes dx of the FF layers, Adam and elementwise
    work."""
    return sum(c.cost.flops * c.count
               for c in job_kernel_calls(model, traffic))


def layer_steps_per_job(model: dict, traffic: dict) -> int:
    """Optimizer steps of all layers in one job."""
    steps = -(-traffic["n_train"] // model["batch_size"]) \
        * max(model["epochs"] // model["splits"], 1)
    return steps * model["splits"] * (len(model["layer_sizes"]) - 1)


def train_samples_per_job(model: dict, traffic: dict) -> int:
    """Samples that pass through every layer in one job."""
    return traffic["n_train"] * max(model["epochs"] // model["splits"], 1) \
        * model["splits"]
