"""tools/tpu_kernel_smoke.py's scan checks on the CPU (small shapes,
interpreted): they pass the kernels as they are, and the float32 one
refuses a scan broken as benchmark/controls/granite_hybrid.py breaks
it, which is what makes the full run on the chip a guard for
``ssd_fwd`` / ``ssd_bwd`` there."""

import os
import sys

import pytest

from benchmark.controls import granite_hybrid as controls
from dlrover_tpu.ops import ssd as ssd_module

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import tpu_kernel_smoke  # noqa: E402


def _scan_checks(monkeypatch):
    monkeypatch.setattr(tpu_kernel_smoke, "SMALL", True)
    tpu_kernel_smoke.RESULTS.clear()
    tpu_kernel_smoke.ssd_checks()
    return {r["kernel"]: r for r in tpu_kernel_smoke.RESULTS}


def test_scan_checks_pass_the_kernels_as_they_are(monkeypatch):
    got = _scan_checks(monkeypatch)
    assert set(got) == {"ssd_fwd_bwd_f32", "ssd_fwd_bwd_bf16"}
    assert all(r["ok"] for r in got.values()), got
    assert got["ssd_fwd_bwd_f32"]["max_abs_err"] < 1e-5


@pytest.mark.parametrize("name,attribute,broken", [
    ("no_carry", "ssd", controls._scan_without_carry),
    ("state_bf16", "_fwd_kernel", controls._kernel_with_bf16_state),
])
def test_float32_check_refuses_a_broken_scan(monkeypatch, name, attribute, broken):
    monkeypatch.setattr(
        ssd_module, attribute, broken(getattr(ssd_module, attribute))
    )
    got = _scan_checks(monkeypatch)
    assert not got["ssd_fwd_bwd_f32"]["ok"], (name, got)
    assert "relative error" in got["ssd_fwd_bwd_f32"]["error"]


def _conv_check(monkeypatch):
    monkeypatch.setattr(tpu_kernel_smoke, "SMALL", True)
    tpu_kernel_smoke.RESULTS.clear()
    tpu_kernel_smoke.ssm_conv_checks()
    (got,) = tpu_kernel_smoke.RESULTS
    assert got["kernel"] == "ssm_conv_fwd_bwd_bf16"
    return got


def test_conv_check_passes_the_kernels_as_they_are(monkeypatch):
    got = _conv_check(monkeypatch)
    assert got["ok"], got
    assert got["max_abs_err"] <= 2.0 ** -8 < got["limit"]


def test_conv_check_refuses_a_backward_that_does_not_look_ahead(monkeypatch):
    """``dx[t]`` reads ``g`` at ``t`` and up to K-1 rows AFTER it; a
    kernel that reads ``g[t]`` alone keeps every shape and the
    forward's loss, and only a check of the gradients sees it."""
    from dlrover_tpu.ops import causal_conv

    real = causal_conv._ahead
    monkeypatch.setattr(
        causal_conv, "_ahead",
        # x's taps start _HALO - (K-1) rows in, g's shifts under K.
        lambda v, offset, rows: real(v, offset if offset >= 8 else 0, rows),
    )
    got = _conv_check(monkeypatch)
    assert not got["ok"], got
    assert "relative error" in got["error"]


def _flash_checks(monkeypatch):
    monkeypatch.setattr(tpu_kernel_smoke, "SEQ", 128)
    tpu_kernel_smoke.RESULTS.clear()
    tpu_kernel_smoke.flash_checks()
    return {r["kernel"]: r for r in tpu_kernel_smoke.RESULTS}


def test_flash_checks_pass_and_hold_lse(monkeypatch):
    got = _flash_checks(monkeypatch)
    assert all(r["ok"] for r in got.values()), got
    assert got["flash_lse_fwd_bwd"]["max_abs_err"] < 1e-4


def test_lse_check_refuses_a_backward_that_drops_the_lse_cotangent(
    monkeypatch,
):
    """``flash_lse_fwd_bwd`` is the one check that reads ``lse`` and
    sends a cotangent through it (the backward kernel's ``delta``
    row): a backward that loses that cotangent passes every other
    flash check and fails this one."""
    # ``dlrover_tpu.ops.flash_attention`` the attribute is the
    # re-exported function; the module is in sys.modules.
    fa = sys.modules["dlrover_tpu.ops.flash_attention"]
    real = fa._bwd

    def without_g_lse(*args, g_lse=None, **kwargs):
        return real(*args, g_lse=None, **kwargs)

    monkeypatch.setattr(fa, "_bwd", without_g_lse)
    got = _flash_checks(monkeypatch)
    assert not got.pop("flash_lse_fwd_bwd")["ok"]
    assert all(r["ok"] for r in got.values()), got


@pytest.mark.parametrize("lost", ["first", "last"])
def test_subtile_check_refuses_a_backward_that_skips_a_live_subtile(
    monkeypatch, lost
):
    """``flash_bwd_subtiles`` runs crossing blocks at every shape the
    cells have (one diagonal block, several, a band's edge, padding,
    an offset): a kernel whose loop over a key sub-tile's live query
    sub-tiles starts one late, or ends one early, fails it."""
    fa = sys.modules["dlrover_tpu.ops.flash_attention"]
    real = fa._live_q_tiles

    def one_short(k0, q0, *args):
        lo, hi = real(k0, q0, *args)
        if isinstance(k0, int) and isinstance(q0, int):
            return lo, hi  # the area the event reports stays right
        return (lo + 1, hi) if lost == "first" else (lo, hi - 1)

    monkeypatch.setattr(fa, "_live_q_tiles", one_short)
    got = _flash_checks(monkeypatch)
    assert not got["flash_bwd_subtiles"]["ok"]
    assert "Mismatched elements" in got["flash_bwd_subtiles"]["error"]


def _kda_checks(monkeypatch):
    monkeypatch.setattr(tpu_kernel_smoke, "SMALL", True)
    tpu_kernel_smoke.RESULTS.clear()
    tpu_kernel_smoke.kda_checks()
    return {r["kernel"]: r for r in tpu_kernel_smoke.RESULTS}


def test_kda_checks_pass_the_rule_as_it_is(monkeypatch):
    got = _kda_checks(monkeypatch)
    assert set(got) == {"kda_fwd_bwd_f32", "kda_fwd_bwd_bf16"}
    assert all(r["ok"] for r in got.values()), got
    assert got["kda_fwd_bwd_f32"]["max_abs_err"] < 2e-5


@pytest.mark.parametrize("name", ["no_carry", "no_delta"])
def test_float32_check_refuses_a_broken_rule(monkeypatch, name):
    """The rule broken as benchmark/controls/kimi_linear.py breaks it:
    a state that does not cross a chunk boundary, the delta term
    dropped."""
    from benchmark.controls import kimi_linear as kimi_controls
    from dlrover_tpu.ops import kda as kda_module

    broken = {
        "no_carry": kimi_controls._rule_without_carry(kda_module.kda),
        "no_delta": kimi_controls._rule_without_delta(kda_module),
    }[name]
    monkeypatch.setattr(kda_module, "kda", broken)
    got = _kda_checks(monkeypatch)
    assert not got["kda_fwd_bwd_f32"]["ok"], (name, got)
    assert "relative error" in got["kda_fwd_bwd_f32"]["error"]


def test_two_head_sizes_check_passes_and_refuses_the_wrong_scale(monkeypatch):
    got = _flash_checks(monkeypatch)
    assert got["flash_qk192_v128"]["ok"], got["flash_qk192_v128"]
    fa = sys.modules["dlrover_tpu.ops.flash_attention"]
    real = fa._fwd

    def scaled_by_the_values_head(q, k, v, causal, window, scale, *rest, **kw):
        if v.shape[-1] != q.shape[-1]:
            scale = scale * (q.shape[-1] / v.shape[-1]) ** 0.5
        return real(q, k, v, causal, window, scale, *rest, **kw)

    monkeypatch.setattr(fa, "_fwd", scaled_by_the_values_head)
    got = _flash_checks(monkeypatch)
    assert not got.pop("flash_qk192_v128")["ok"]
    assert all(r["ok"] for r in got.values()), got
