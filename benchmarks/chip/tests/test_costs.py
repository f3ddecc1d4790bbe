"""The cost models against counts made by hand at small shapes."""
import pytest

from harness import BENCH, load_json, load_module

sim = load_module(BENCH / "costs" / "sim_round.py")
air = load_module(BENCH / "costs" / "aircomp.py")
ps = load_module(BENCH / "costs" / "ps_step.py")
PEAKS = load_json(BENCH / "peaks.json")["TPU v5 lite"]

CFG = {"num_clients": 3, "clients_per_round": 2, "batch_size": 4,
       "data": {"dim": 5, "num_classes": 2, "test": 6}}


def test_sim_round_flops_by_hand():
    # one forward of a batch: 2*4*5*2 = 80; eval: 2*6*5*2 = 120
    # exact-K: 2 clients x (fwd+bwd 160) + 2 ascent + 2 metric forwards
    assert sim.flops_per_seed_round("ca_afl", CFG) == 2 * 160 + 4 * 80 + 120
    # gca: all 3 clients fwd+bwd, all 3 ascent forwards
    assert sim.flops_per_seed_round("gca", CFG) == 3 * 160 + 3 * 80 + 120


@pytest.mark.parametrize("kernel,ops,nbytes", [
    # C=2, M=3, batch 1: x 6, w 2, z 3 read, 3 written (f32)
    ("analog", 2 * 6 + 9, 4 * (6 + 2 + 3 + 3)),
    ("quantized", 6 * 6 + 9, 4 * (12 + 4 + 3 + 3)),
    ("sparse", 4 * 6 + 9, 4 * (6 + 4 + 3 + 3)),
])
def test_aircomp_launch_by_hand(kernel, ops, nbytes):
    assert air.launch(kernel, 1, 2, 3) == (ops, nbytes)
    assert air.launch(kernel, 5, 2, 3) == (5 * ops, 5 * nbytes)


def test_aircomp_is_memory_bound_at_the_paper_shape():
    flops, nbytes = air.launch("analog", 25, 40, 7850)
    assert air.least_seconds(flops, nbytes, PEAKS) == nbytes / 8.19e11


def test_sweep_launches_follow_the_methods():
    cfg = dict(CFG, clients_per_round=2)
    m = 5 * 2 + 2
    assert air.sweep_launches("ca_afl", "analog", 10, cfg, 7) == [
        ("analog", 10, 2, m, 7)]
    assert air.sweep_launches("gca", "analog", 5, cfg, 7) == []
    assert air.sweep_launches("gca", "quantized", 5, cfg, 7) == [
        ("quantized", 5, 3, m, 7)]
    assert air.sweep_launches("afl", "digital", 5, cfg, 7) == [
        ("analog", 5, 2, m, 7)]


def test_ps_step_flops_by_hand():
    m = {"hidden_size": 4, "intermediate_size": 6, "head_dim": 2,
         "num_attention_heads": 2, "num_key_value_heads": 1,
         "num_hidden_layers": 3, "vocab_size": 10}
    # per layer: q 4*4, o 4*4, k 4*2, v 4*2, mlp 3*4*6 = 120; head 40
    assert ps.matmul_params(m) == 3 * 120 + 40
    # causal pairs at seq 3: 6; per layer 2 products x 2 flops x 2 heads x 2
    assert ps.attention_fwd_per_sequence(m, 3) == 3 * 2 * 2 * 2 * 2 * 6
    fl = {"rows_per_client": 1, "seq_len": 3, "clients_per_round": 1,
          "num_clients": 2}
    p, att = 400.0, 288.0
    assert ps.flops_per_step(m, fl) == (6 * p * 3 + 3 * att
                                        + 2 * p * 2 * 3 + 2 * att)


def test_qwen2_matmul_params_at_published_widths():
    m = load_json(BENCH / "configs" / "qwen2-0.5b.json")["model"]
    # 24 x (896*896*2 + 896*128*2 + 3*896*4864) + 896*151936
    assert ps.matmul_params(m) == 24 * (1605632 + 229376 + 13074432) \
        + 136134656
