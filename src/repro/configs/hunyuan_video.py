"""hunyuan-video-dit (paper arch, HunyuanVideo;
https://huggingface.co/tencent/HunyuanVideo, transformer config): the 40
single-stream blocks (``num_single_layers``; the 20 dual-stream blocks are
absent), d=3072 24H x 128, d_ff=12288 (MLP ratio 4); 256 text + 32768
video tokens (the paper's 33K setting, the 1.5x end-to-end target).  Full
FlashOmni Update-Dispatch applies."""
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="hunyuan-video-dit", family="dit", n_layers=40, d_model=3072,
    n_heads=24, n_kv_heads=24, d_ff=12288, vocab=0, head_dim=128,
    n_text_tokens=256, patch_dim=64,
    skip_shapes=("train_4k", "prefill_32k", "decode_32k", "long_500k"),
)

SMOKE = ArchConfig(
    name="hunyuan-smoke", family="dit", n_layers=3, d_model=64, n_heads=2,
    n_kv_heads=2, d_ff=128, vocab=0, head_dim=32, n_text_tokens=32,
    patch_dim=16, remat=False,
)
