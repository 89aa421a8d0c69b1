"""Operations and bytes of one call of the blockwise dense attention
kernel (``flashomni_dense_attention``: Update and engine-off steps), per
device of the mesh.

Counts are multiply-adds times two, for one block of one request of batch
``b`` at the configuration's sizes.  On a ``(dp, sp)`` mesh each device
attends its share of the query rows (batch over ``dp``, tokens over
``sp``) to every key: the operations split over all ``dp * sp`` devices,
while each device reads the whole K and V of its batch share.  Bytes are
the least the work needs: Q read, K and V read and O written once each.
"""

from __future__ import annotations

BF16 = 2
KERNEL = "flashomni_dense_attention"


def dense_attention_work(s: dict, mesh=(1, 1), b: int = 1) -> tuple:
    """``(flops, bytes)`` of one call on one device of ``mesh``."""
    n = s["n_text_tokens"] + s["n_image_tokens"]
    f = s["n_heads"] * s["head_dim"]
    dp, sp = mesh
    flops = 4 * b * n * n * f / (dp * sp)
    byts = BF16 * b / dp * f * (2 * n / sp + 2 * n)
    return flops, byts
