"""Config registry + analytic parameter-count sanity for all 10 archs."""
import pytest

from repro.configs import ASSIGNED_ARCHS, at_depth, get_config, reduced
from repro.configs.shapes import LONG_CONTEXT_OK, LONG_CONTEXT_SKIP, SHAPES, cells

# published parameter counts (approx, in billions) for sanity bands
EXPECTED_B = {
    "mamba2-2.7b": (2.2, 3.2),
    "deepseek-7b": (6.0, 8.0),
    "gemma-2b": (2.0, 3.3),        # incl. 256k vocab embeddings
    "qwen3-8b": (7.0, 9.0),
    "gemma2-27b": (24.0, 30.0),
    "mixtral-8x22b": (130.0, 150.0),
    "deepseek-v2-lite-16b": (13.0, 17.5),
    "musicgen-large": (1.5, 4.0),
    "hymba-1.5b": (1.2, 2.0),
    "internvl2-76b": (62.0, 80.0),  # backbone only (ViT is stubbed)
}


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_registered_and_valid(arch):
    cfg = get_config(arch)
    cfg.validate()
    assert cfg.name == arch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_counts_in_band(arch):
    cfg = get_config(arch)
    n = cfg.param_counts()["total"] / 1e9
    lo, hi = EXPECTED_B[arch]
    assert lo <= n <= hi, f"{arch}: {n:.2f}B outside [{lo},{hi}]"


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_scan_groups_cover_all_layers(arch):
    cfg = get_config(arch)
    groups = cfg.scan_groups()
    assert sum(g.num_layers for g in groups) == cfg.num_layers
    # specs reconstructed from groups match layer_specs order
    flat = []
    for g in groups:
        for _ in range(g.repeats):
            flat.extend(g.unit)
    assert tuple(flat) == cfg.layer_specs()


def test_gemma2_alternating_pattern():
    cfg = get_config("gemma2-27b")
    specs = cfg.layer_specs()
    assert specs[0].window == 4096 and specs[1].window is None
    groups = cfg.scan_groups()
    assert len(groups) == 1 and len(groups[0].unit) == 2 and groups[0].repeats == 23


def test_deepseek_v2_first_dense():
    cfg = get_config("deepseek-v2-lite-16b")
    specs = cfg.layer_specs()
    assert specs[0].mlp == "dense" and all(s.mlp == "moe" for s in specs[1:])


def test_hymba_global_layers():
    cfg = get_config("hymba-1.5b")
    specs = cfg.layer_specs()
    for i, s in enumerate(specs):
        assert (s.window is None) == (i in (0, 15, 31))


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_configs_valid(arch):
    r = reduced(get_config(arch))
    r.validate()
    assert r.d_model <= 256 and r.num_layers <= 4


def test_cell_enumeration():
    cs = list(cells(ASSIGNED_ARCHS))
    assert len(cs) == 34  # 10 archs x 3 shapes + 4 long_500k
    for a in LONG_CONTEXT_OK:
        assert (a, "long_500k") in cs
    for a in LONG_CONTEXT_SKIP:
        assert (a, "long_500k") not in cs


def test_kv_bytes_per_token_matches_paper_scale():
    # paper/Splitwise reference: llama2-70b ~0.32 MB/token at fp16
    cfg = get_config("llama2-70b")
    assert 2.5e5 < cfg.kv_bytes_per_token() < 4e5
    # MLA compression: deepseek-v2-lite is ~an order of magnitude smaller
    # per layer than equivalent GQA
    v2 = get_config("deepseek-v2-lite-16b")
    per_layer = v2.kv_bytes_per_token() / v2.num_layers
    gqa_equiv = 2 * 16 * 128 * 2  # kv=16 heads of 128 at bf16
    assert per_layer < gqa_equiv / 5


@pytest.mark.parametrize("arch,layers", [("qwen3-8b", 16), ("gemma2-27b", 4),
                                         ("hymba-1.5b", 4),
                                         ("deepseek-v2-lite-16b", 5)])
def test_at_depth_keeps_widths_and_whole_periods(arch, layers):
    """The chip's depth cut changes the layer count only: every width is
    the published one, the kept layers are the published stack's first
    ones, and each scan group is a unit of the full stack."""
    full = get_config(arch)
    cut = at_depth(full, layers)
    assert cut.num_layers == layers
    assert cut.layer_specs() == full.layer_specs()[:layers]
    for k in ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "vocab_size", "n_experts", "expert_d_ff", "kv_lora_rank",
              "ssm_state", "window_pattern"):
        assert getattr(cut, k) == getattr(full, k), k
    units = {g.unit for g in full.scan_groups()}
    assert all(g.unit in units for g in cut.scan_groups())


@pytest.mark.parametrize("arch,layers", [("gemma2-27b", 3),
                                         ("deepseek-v2-lite-16b", 1),
                                         ("qwen3-8b", 0), ("qwen3-8b", 37)])
def test_at_depth_refuses_cuts_that_split_the_pattern(arch, layers):
    with pytest.raises(ValueError):
        at_depth(get_config(arch), layers)
