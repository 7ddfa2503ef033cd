"""HLO-parser tests: trip-count-corrected FLOPs on known programs, the
synthetic-HLO fixture, and the roofline term arithmetic."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry as REG
from repro.roofline import hlo_parse as H
from repro.roofline import model as RF


def _flops_of(fn, *args):
    comp = jax.jit(fn).lower(*args).compile()
    return H.analyze(comp.as_text())


def test_plain_matmul_flops_exact():
    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 32), jnp.float32)
    an = _flops_of(lambda a, b: a @ b, a, b)
    assert an["flops"] == 2 * 64 * 128 * 32


def test_scan_multiplies_by_trip_count():
    w = jnp.ones((64, 64), jnp.float32)
    x = jnp.ones((8, 64), jnp.float32)

    def f(x, w):
        def step(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(step, x, None, length=17)
        return y

    an = _flops_of(f, x, w)
    assert an["flops"] == 17 * 2 * 8 * 64 * 64
    assert an["unknown_trip_loops"] == 0
    # XLA's own cost_analysis counts the body once — this is the bug the
    # parser exists to fix; keep the regression visible:
    comp = jax.jit(f).lower(x, w).compile()
    xla_flops = comp.cost_analysis().get("flops", 0.0)
    assert xla_flops <= an["flops"] / 16


def test_nested_scan_multiplies():
    w = jnp.ones((16, 16), jnp.float32)
    x = jnp.ones((4, 16), jnp.float32)

    def f(x, w):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ w, None
            c, _ = jax.lax.scan(inner, c, None, length=5)
            return c, None
        y, _ = jax.lax.scan(outer, x, None, length=3)
        return y

    an = _flops_of(f, x, w)
    assert an["flops"] == 3 * 5 * 2 * 4 * 16 * 16


def test_synthetic_collective_fixture():
    hlo = """
HloModule test, num_partitions=4

%add (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(%a, %b)
}

ENTRY %main (p: f32[128,256]) -> f32[128,256] {
  %p = f32[128,256]{1,0} parameter(0)
  %ag = f32[128,256]{1,0} all-gather(%p), dimensions={0}
  ROOT %ar = f32[128,256]{1,0} all-reduce(%ag), to_apply=%add
}
"""
    an = H.analyze(hlo)
    assert an["collective_bytes"]["all-gather"] == 128 * 256 * 4
    assert an["collective_bytes"]["all-reduce"] == 128 * 256 * 4
    assert an["collective_bytes_total"] == 2 * 128 * 256 * 4


def test_tuple_shape_bytes():
    assert H._shape_bytes("(f32[2,3]{1,0}, bf16[4])") == 2 * 3 * 4 + 4 * 2
    assert H._shape_bytes("pred[]") == 1
    assert H._shape_bytes("s32[]") == 4


def test_roofline_terms_and_dominance():
    an = {"flops": 197e12, "hbm_bytes": 819e9 / 2,
          "collective_bytes_total": 50e9 / 4}
    t = RF.terms_from_analysis(an, n_chips=4, device_kind="TPU v5 lite",
                               model_flops=4 * 197e12 / 2)
    assert t.dominant == "compute"
    assert abs(t.compute_s - 1.0) < 1e-9
    assert abs(t.memory_s - 0.5) < 1e-9
    assert abs(t.collective_s - 0.25) < 1e-9
    assert abs(t.useful_flops_ratio - 0.5) < 1e-9
    assert abs(t.mfu_at_bound - 0.5) < 1e-9


def test_roofline_peaks_refuse_an_unknown_device_kind():
    assert RF.peaks("TPU v5 lite").flops == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        RF.terms_from_analysis({"flops": 1.0, "hbm_bytes": 1.0,
                                "collective_bytes_total": 0.0},
                               n_chips=1, device_kind="cpu")


def test_model_flops_train_vs_decode():
    cfg = REG.get_config("yi-9b")
    train = RF.model_flops(cfg, REG.get_shape("train_4k"))
    dec = RF.model_flops(cfg, REG.get_shape("decode_32k"))
    # train: 6*N*B*S; decode: 2*N*B
    assert train / dec == pytest.approx(
        (6 * 256 * 4096) / (2 * 128), rel=1e-6)


def test_attention_scan_flop_ratio_matches_tiles():
    """The compiled LTM attention executes T(n)/n^2 of the BB dot-FLOPs —
    the paper's improvement, visible in the compiled artifact."""
    from benchmarks.bench_attention import run
    r = run(seqs=(512,), block=64)[0]
    n = 512 // 64
    expect = (n * n) / (n * (n + 1) / 2)
    assert r["I_flops"] == pytest.approx(expect, rel=1e-6)
