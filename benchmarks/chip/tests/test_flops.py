"""The FLOP count per token is a function of the configuration's shapes
alone, and its parameter count agrees with the program's own."""
import pytest

from chipbench import spec
from chipbench.cell import program_api
from chipbench.flops import flops_per_token
from chipbench.reference import family

CONFIGS = ["olmo-1b"]


def _cfg(name):
    cells = [w for w in spec.benchmark()["workloads"] if w["config"] == name]
    return spec.cell(cells[0]["name"])["cfg"]


@pytest.mark.parametrize("name", CONFIGS)
def test_matmul_params_match_the_programs_parameter_count(name):
    cfg = _cfg(name)
    mod = family(cfg["reference"])
    api = program_api(cfg, mod)
    embed = cfg["vocab"] * cfg["d_model"]
    head = embed if cfg["tie_embeddings"] else 0  # the tied head is the embedding
    assert mod.matmul_params(cfg) == api.param_count() - embed + head


@pytest.mark.parametrize("name", CONFIGS)
def test_flops_per_token_from_shapes(name):
    cfg = _cfg(name)
    d, h, kv, dh, ff, L = (cfg[k] for k in
                           ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "n_layers"))
    mlp = 3 * d * ff
    attn = d * (h + 2 * kv) * dh + h * dh * d
    n = L * (attn + mlp) + d * cfg["vocab"]
    seq = 512
    assert flops_per_token(cfg, seq, family(cfg["reference"]).matmul_params(cfg)) == (
        6 * n + 12 * L * seq * h * dh)


def test_olmo_flops_per_token_value():
    cfg = _cfg("olmo-1b")
    f = flops_per_token(cfg, 512, family("dense").matmul_params(cfg))
    # 6 x 707.0M matmul parameters (9 layers and the tied head) + 12 x 9 x 512
    # x 16 x 128 (attention)
    assert f == 4355260416
