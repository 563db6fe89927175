"""Small presets of each configuration and mix that a CPU test can run:
the configuration's own kinds of layer (attention, MLP, norm) at tiny
widths, and the mix with short rows."""
import copy

from chipbench import spec


def tiny(cell_name, dtype="float32"):
    c = spec.cell(cell_name)
    cfg = copy.deepcopy(c["cfg"])
    gqa = cfg["n_kv_heads"] != cfg["n_heads"]
    cfg.update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2 if gqa else 4,
               head_dim=16, d_ff=128, vocab=256, param_dtype=dtype, compute_dtype=dtype)
    mix = copy.deepcopy(c["mix"])
    mix.update(seq_len=32)
    return cfg, mix
