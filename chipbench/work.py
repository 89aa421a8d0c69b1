"""Operations and bytes of a denoising step, from shapes and live fractions.

Counts are multiply-adds times two, at the configuration's sizes, for one
request of batch ``b``.  A dense (and an Update) step counts every
product.  A Dispatch step counts the K and V projections and the MLP
whole, the Q and O projections at the step's ``density`` (the live share
of (block, head) pairs) and attention at ``pair_live`` (the live share of
(query block, key block, head) triples): the program's own counters for
the step.  GEMM-Q projects every row block live in any head, so its count
errs low, never high.

On a mesh ``(dp, sp)`` a kernel's work per device is its share: attention
is split over all ``dp * sp`` devices, the sparse GEMMs over ``dp`` only
(each sequence shard projects its data shard's rows whole).
"""

from __future__ import annotations

BF16 = 2


def _dims(s: dict):
    n = s["n_text_tokens"] + s["n_image_tokens"]
    return n, s["d_model"], s["n_heads"] * s["head_dim"], s["d_ff"]


def layer_flops(s: dict, b: int = 1) -> dict:
    """One block of one dense step, by part."""
    n, d, f, ff = _dims(s)
    return {"q": 2 * b * n * d * f, "kv": 4 * b * n * d * f,
            "attention": 4 * b * n * n * f, "o": 2 * b * n * f * d,
            "mlp": 4 * b * n * d * ff, "adaln": 2 * b * d * 6 * d}


def step_flops(s: dict, n_layers: int, kind: str, density: float = 1.0,
               pair_live: float = 1.0, b: int = 1) -> float:
    """The whole model for one step of ``kind`` (dense, update, dispatch)."""
    lf = layer_flops(s, b)
    if kind == "dispatch":
        lf["q"] *= density
        lf["o"] *= density
        lf["attention"] *= pair_live
    d, pd, nv = s["d_model"], s["patch_dim"], s["n_image_tokens"]
    outside = 2 * b * (nv * pd * d + 256 * d + d * d + 2 * d * d + nv * d * pd)
    return n_layers * sum(lf.values()) + outside


def request_flops(s: dict, n_layers: int, trace: list, b: int = 1) -> float:
    """A request's operations from its per-step trace of the program."""
    return sum(step_flops(s, n_layers, st["kind"], st["density"],
                          1.0 - st["pair_sparsity"], b) for st in trace)


def kernel_work(s: dict, kernel: str, density: float, pair_live: float,
                mesh=(1, 1), b: int = 1) -> tuple:
    """``(flops, bytes)`` of one Dispatch-step call of a Pallas kernel in
    one block, on one device of the mesh.  Bytes are the least the work
    needs: each operand read once and each result written once."""
    n, d, f, _ = _dims(s)
    dp, sp = mesh
    if kernel == "gemm_q":
        flops = 2 * b * n * d * f * density
        byts = BF16 * (b * n * density * (d + f) + d * f)
        return flops / dp, byts / dp
    if kernel == "gemm_o":
        flops = 2 * b * n * f * d * density
        # live rows of O read, weights read, the bias read and written
        byts = BF16 * (b * n * density * f + f * d + 2 * b * n * d)
        return flops / dp, byts / dp
    if kernel == "csr_attention":
        flops = 4 * b * n * n * f * pair_live
        # the least traffic: live Q rows and K, V read once, O written
        byts = BF16 * b * n * f * (2 + 2 * density)
        return flops / (dp * sp), byts / (dp * sp)
    raise KeyError(f"no work count for kernel {kernel!r}")
