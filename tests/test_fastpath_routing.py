"""Fastpath routing (tier-1): ``analog_matmul`` through the fused
single-launch serving kernels vs the composed multi-op chain vs the
fused jnp oracle, across input-accumulation x parasitics x slicing x
partitions; the must-refuse-to-fuse fallbacks; and the
``fuse_signature`` compile identity the per-site-class serving contract
keys on (``repro.hw.fused_site_classes``).

Exactness policy: kernel and oracle compute the same ADC codes in the
same tile order, but the float32 bits of the partition sum and the final
dequant multiply depend on how each side's compiler contracts and
fuses them, which shifts between XLA releases and backends (a
multi-partition design drifts by 2 ULP under XLA-CPU in jax 0.9).  So
under jit the kernel is pinned to the oracle within a small fraction of
one ADC code (:func:`_assert_within_codes`), far below the one-code
step of any real disagreement.  The bitwise pin is kept where the bits
are structural: a refused design's fallback IS the composed chain.
Eagerly, XLA dispatches the chain as separate ops (a 1-2 ULP artifact,
never an ADC code flip), so eager checks use float tolerance.
Fused-vs-composed compares two *different* op orders over the same ADC
codes: float-level agreement, not bitwise.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import analog as A
from repro.core.analog import AnalogSpec, fuse_signature
from repro.core.calibrate import calibrate_adc_for_matmul
from repro.core.errors import ErrorModel
from repro.core.mapping import MappingConfig
from repro.hw import Profile, Rule, fused_site_classes

BASE = A.design_a(error=ErrorModel())
SLICED = dataclasses.replace(
    BASE, mapping=MappingConfig(scheme="differential", weight_bits=8,
                                bits_per_cell=2, on_off_ratio=1e4))

#: every fuse-eligible corner of input_accum x parasitics x slicing x
#: partitions, with the compile signature each must lower to
ROUTED = [
    ("designA", BASE, ("linear", 1, 7, 8, None, None)),
    ("designA_parasitic", dataclasses.replace(BASE, r_hat=1e-4),
     ("parasitic", 1, 7, 8, None, 7)),
    ("digital_accum", dataclasses.replace(BASE, input_accum="digital"),
     ("linear", 1, 7, 8, 7, None)),
    ("sliced", SLICED, ("linear", 4, 2, 8, None, None)),
    ("sliced_digital", dataclasses.replace(SLICED, input_accum="digital"),
     ("linear", 4, 2, 8, 7, None)),
    ("multi_partition", dataclasses.replace(BASE, max_rows=96),
     ("linear", 1, 7, 8, None, None)),
]

#: specs that must refuse to fuse and fall back to the composed chain
REFUSED = [
    ("parasitic_digital", dataclasses.replace(
        BASE, input_accum="digital", r_hat=1e-4)),
    ("offset_scheme", dataclasses.replace(
        BASE, mapping=MappingConfig(scheme="offset", weight_bits=8,
                                    on_off_ratio=1e4),
        input_accum="digital")),
    ("uncalibrated_adc", dataclasses.replace(
        BASE, adc=dataclasses.replace(BASE.adc, style="fpg"))),
]


#: kernel-vs-oracle bound, in ADC codes of the finest slice.  Both sides
#: hold identical codes, so what is left is float32 reassociation of the
#: partition sum and the dequant multiply: measured at 4.4e-5 codes
#: (2 ULP) for the multi-partition design.  1e-3 codes is 20x that and
#: still 1000x below a single code flip, the signature of a real
#: kernel/oracle disagreement.
MAX_CODE_DRIFT = 1e-3


def _code_step(spec, aw, x, lo, hi):
    """Output-space size of one ADC code of the finest slice:
    ``gain * w_scale * x_scale * lsb``."""
    from repro.core.quant import quantize_acts

    m = spec.mapping
    gain = (m.levels_per_cell - 1) / (1.0 - m.g_min)
    x_scale = quantize_acts(x.astype(jnp.float32), spec.input_bits).scale
    lsb = jnp.min((hi - lo) / (2 ** spec.adc.bits - 1))
    return float(gain * aw.w_scale * x_scale * lsb)


def _assert_within_codes(y_k, y_o, step, codes=MAX_CODE_DRIFT):
    d = np.abs(np.asarray(y_k) - np.asarray(y_o)) / step
    assert float(d.max()) <= codes, f"max drift {float(d.max()):.3e} codes"


def _case(spec, m=4, k=200, n=48, seed=0):
    kw_, kx = jax.random.split(jax.random.PRNGKey(seed))
    w = jax.random.normal(kw_, (k, n)) * 0.1
    x = jax.random.normal(kx, (m, k))
    aw = A.program(w, spec, key=jax.random.PRNGKey(1))
    lo, hi = calibrate_adc_for_matmul(x, aw, spec)
    return x, aw, lo, hi


@pytest.mark.parametrize("tag,spec,sig", ROUTED, ids=[t for t, _, _ in ROUTED])
def test_fused_routes_and_agrees(tag, spec, sig):
    fspec = dataclasses.replace(spec, fused="kernel")
    assert A._maybe_pallas_fastpath(fspec, False)
    assert not A._maybe_pallas_fastpath(fspec, True)   # collection composes
    assert fuse_signature(fspec) == sig
    assert fuse_signature(spec) is None                # fused="off"

    x, aw, lo, hi = _case(spec)
    if tag == "multi_partition":
        assert aw.g_pos.shape[1] > 1                   # P really is > 1
    y_c = A.analog_matmul(x, aw, spec, adc_lo=lo, adc_hi=hi)
    arms = {
        mode: jax.jit(lambda x, s=dataclasses.replace(spec, fused=mode):
                      A.analog_matmul(x, aw, s, adc_lo=lo, adc_hi=hi))
        for mode in ("kernel", "oracle")
    }
    y_k, y_o = arms["kernel"](x), arms["oracle"](x)
    _assert_within_codes(y_k, y_o, _code_step(spec, aw, x, lo, hi))
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_c),
                               rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("tag,spec", REFUSED, ids=[t for t, _ in REFUSED])
def test_refuses_to_fuse_and_falls_back(tag, spec):
    fspec = dataclasses.replace(spec, fused="kernel")
    assert fuse_signature(fspec) is None
    x, aw, lo, hi = _case(spec)
    if spec.adc.style != "calibrated":
        lo = hi = None
    y_c = A.analog_matmul(x, aw, spec, adc_lo=lo, adc_hi=hi)
    y_f = A.analog_matmul(x, aw, fspec, adc_lo=lo, adc_hi=hi)
    # the fallback IS the composed chain: bitwise, not merely close
    np.testing.assert_array_equal(np.asarray(y_f), np.asarray(y_c))


def test_fused_field_validated():
    with pytest.raises(ValueError, match="fused"):
        AnalogSpec(fused="mosaic")
    for mode in ("off", "kernel", "oracle"):
        assert AnalogSpec(fused=mode).fused == mode


def test_fused_eager_matches_jit_to_float_tolerance():
    """Eager dispatch may re-associate the dequant multiply (separate-op
    XLA fusion) — bounded to ULP-scale, never an ADC code flip."""
    spec = dataclasses.replace(BASE, fused="kernel")
    x, aw, lo, hi = _case(BASE)
    y_e = A.analog_matmul(x, aw, spec, adc_lo=lo, adc_hi=hi)
    y_j = jax.jit(lambda x: A.analog_matmul(x, aw, spec,
                                            adc_lo=lo, adc_hi=hi))(x)
    np.testing.assert_allclose(np.asarray(y_e), np.asarray(y_j),
                               rtol=1e-5, atol=1e-6)


def test_fuse_signature_groups_sites_not_layers():
    """A profile with two ADC widths and a parasitic MLP band lowers to
    exactly three fused programs, whatever the site and layer count —
    the one-compile-per-site-class identity the serving contract pins."""
    spec6 = dataclasses.replace(
        BASE, adc=dataclasses.replace(BASE.adc, bits=6))
    par = dataclasses.replace(BASE, r_hat=1e-4)
    prof = Profile(rules=(
        Rule("attn.*", dataclasses.replace(BASE, fused="kernel")),
        Rule("w_up", dataclasses.replace(spec6, fused="kernel")),
        Rule("w_down", dataclasses.replace(par, fused="kernel"),
             layers=(0, 2)),
        Rule("w_down", dataclasses.replace(BASE, fused="kernel"),
             layers=(2, 4)),
    ), default=dataclasses.replace(BASE, fused="kernel"))
    sites = ["wq", "wk", "wv", "wo", "w_up", "w_down", "w_gate"]
    groups = fused_site_classes(prof, sites, n_layers=4)
    assert set(groups) == {
        ("linear", 1, 7, 8, None, None),
        ("linear", 1, 7, 6, None, None),
        ("parasitic", 1, 7, 8, None, 7),
    }
    assert groups[("linear", 1, 7, 6, None, None)] == ["w_up"]
    assert groups[("parasitic", 1, 7, 8, None, 7)] == ["w_down"]
    # w_down fuses differently across its two layer bands: it appears in
    # BOTH the parasitic and the plain linear class
    assert "w_down" in groups[("linear", 1, 7, 8, None, None)]
    # a refusing profile contributes no classes
    off = Profile(rules=(Rule("attn.*", BASE),), default=BASE)
    assert fused_site_classes(off, sites, n_layers=4) == {}
