"""musicgen-medium: MusicGen's decoder [arXiv:2306.05284; the Hugging Face
config of facebook/musicgen-medium].

Four EnCodec codebooks of 2048 codes at 50 Hz in the delay pattern (one
summed embedding per position, one head per codebook), sinusoidal
positions, pre-LN layers of causal self-attention, cross-attention to
the frozen T5-base text encoder's states (width 768, projected to the
model width with bias) and an exact-GELU FFN; LayerNorms with weight and
bias; no bias on any attention or FFN projection."""
from ..models.config import ArchConfig

CONFIG = ArchConfig(
    name="musicgen-medium", family="audio", n_layers=48, d_model=1536,
    n_heads=24, n_kv_heads=24, head_dim=64, d_ff=6144, vocab=2048,
    mlp_act="gelu_exact", norm="layernorm", rope="abs_sin",
    frontend="codebooks", n_codebooks=4, cond_dim=768)
