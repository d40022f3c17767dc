"""The analog in-situ MVM simulator — the paper's contribution as a
composable JAX op.

``program`` maps a float weight matrix onto (error-perturbed) conductance
stacks per a :class:`MappingConfig`; ``analog_matmul`` then executes
``y ~= x @ W`` through the full analog pipeline:

  quantize x -> input bit planes -> per-(K-partition, slice) analog dot
  products (optionally through the parasitic bit-line circuit) -> analog
  differential subtraction (differential scheme) -> ADC per digitized
  quantity -> shift-and-add over slices/input bits -> exact affine
  correction for g_min and offsets -> dequantize.

Design notes
------------
* Everything is shaped so XLA sees dense matmuls: bit planes are (B, M, K)
  and conductance stacks (S, P, rows, N); the hot path (differential,
  unsliced, analog input accumulation — the paper's Design A) reduces to a
  single integer-valued matmul per K-partition plus an ADC, and has a fused
  Pallas kernel (``repro.kernels``) selected via ``use_pallas``.
* "Program-time" cell errors are sampled once from an explicit key in
  ``program``; repeated inference trials vmap over keys.
* Calibration (Sec. 6.2) runs ``analog_matmul(..., collect=True)`` which
  returns per-slice pre-ADC percentile ranges instead of applying an ADC.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import adc as adc_lib
from repro.core import parasitics
from repro.core.errors import DriftModel, ErrorModel, FaultModel
from repro.core.mapping import (
    MappingConfig,
    ProgrammedCodes,
    ProgrammedWeights,
    codes_to_weights,
    program_int_codes,
)
from repro.core.quant import (
    QuantizedTensor,
    bit_planes,
    n_input_planes,
    quantize_acts,
    quantize_weights,
)


@dataclasses.dataclass(frozen=True)
class AnalogSpec:
    """Full static description of one analog core design point."""

    mapping: MappingConfig = dataclasses.field(default_factory=MappingConfig)
    adc: adc_lib.ADCConfig = dataclasses.field(default_factory=adc_lib.ADCConfig)
    error: ErrorModel = dataclasses.field(default_factory=ErrorModel)
    input_bits: int = 8
    signed_inputs: bool = True
    input_accum: str = "analog"       # "analog" | "digital"
    max_rows: int = 1152
    r_hat: float = 0.0                # normalized parasitic resistance
    use_pallas: bool = False
    fused: str = "off"                # "off" | "kernel" | "oracle"
    compute_dtype: jnp.dtype = jnp.float32
    drift: DriftModel = dataclasses.field(default_factory=DriftModel)
    fault: FaultModel = dataclasses.field(default_factory=FaultModel)

    def __post_init__(self):
        if self.input_accum not in ("analog", "digital"):
            raise ValueError(
                f"AnalogSpec.input_accum must be 'analog' or 'digital', "
                f"got {self.input_accum!r}")
        if self.fused not in ("off", "kernel", "oracle"):
            raise ValueError(
                f"AnalogSpec.fused must be 'off', 'kernel' or 'oracle', "
                f"got {self.fused!r}")
        if self.input_bits < 1:
            raise ValueError(
                f"AnalogSpec.input_bits must be >= 1, got {self.input_bits}")
        if self.max_rows < 1:
            raise ValueError(
                f"AnalogSpec.max_rows must be >= 1, got {self.max_rows}")

    @property
    def parasitics_on(self) -> bool:
        """Static program-structure bit: is the bit-line solve in-graph?

        ``r_hat`` itself is allowed to be a *traced* scalar (the sweep
        engine batches a whole parasitic axis through one compilation),
        but whether the tridiagonal solve exists in the program at all is
        a compile-time property.  Concrete ``r_hat``: on iff nonzero
        (any scalar form — see :func:`parasitics.parasitics_off`).  A
        traced ``r_hat`` always means "on" — only ``r_hat > 0`` points
        are batched; the ``r_hat == 0`` short-circuit is a different
        compiled program, never a traced value.
        """
        return not parasitics.parasitics_off(self.r_hat)

    @property
    def aging_on(self) -> bool:
        """Static program-structure bit: any time-dependent device-state
        process in-graph?  Like :attr:`parasitics_on`, the *kind* of each
        process is compile-time while its magnitude (``drift.nu``,
        ``drift.t``, ``fault.rate``, ``fault.t``) may be traced."""
        return self.drift.kind != "none" or self.fault.kind != "none"

    @property
    def n_planes(self) -> int:
        return n_input_planes(self.input_bits, self.signed_inputs)

    def n_partitions(self, k: int) -> int:
        return max(1, math.ceil(k / self.max_rows))

    def rows_per_partition(self, k: int) -> int:
        return math.ceil(k / self.n_partitions(k))

    def fpg_adc_bits(self, k: int) -> int:
        """Eq. (4)/(5) resolution for this design at matrix depth ``k``.

        One extra weight bit when the analog output is signed: differential
        subtraction (the paper's Table 3 numbers, e.g. Design A's
        B_out = 26.2 = 8 + 8 + log2(1152)) or signed input voltages.
        """
        signed_out = (
            self.mapping.scheme == "differential" or self.signed_inputs
        )
        bw = self.mapping.cell_bits + (1 if signed_out else 0)
        bin_eff = self.input_bits if self.input_accum == "analog" else 1
        return adc_lib.fpg_bits(bw, bin_eff, self.rows_per_partition(k))

    def adc_conversions_per_mvm(self, k: int, n: int) -> int:
        """ADC quantizations for one full-precision MVM (Sec. 2.2/9)."""
        per_bit = 1 if self.input_accum == "analog" else self.n_planes
        return self.n_partitions(k) * self.mapping.n_slices * per_bit * n


#: Paper Design A — the recommended configuration (Table 3).
def design_a(error: Optional[ErrorModel] = None, **kw) -> AnalogSpec:
    return AnalogSpec(
        mapping=MappingConfig(scheme="differential", weight_bits=8,
                              bits_per_cell=None, on_off_ratio=1e4),
        adc=adc_lib.ADCConfig(style="calibrated", bits=8),
        error=error or ErrorModel(),
        input_accum="analog",
        max_rows=1152,
        **kw,
    )


#: Paper Design E — the ISAAC-like offset/FPG baseline (Table 3).
def design_e(error: Optional[ErrorModel] = None, **kw) -> AnalogSpec:
    return AnalogSpec(
        mapping=MappingConfig(scheme="offset", weight_bits=8, bits_per_cell=2),
        adc=adc_lib.ADCConfig(style="calibrated", bits=8),
        error=error or ErrorModel(),
        input_accum="digital",
        max_rows=72,
        **kw,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class AnalogWeights:
    """Programmed conductances + dequantization metadata for one matrix.

    As stored, ``layer`` is ``None``.  Inside a layer scan a site whose
    stack the fused kernel reads in place (:func:`reads_stacked`) keeps
    its layer-stacked ``g_pos``/``g_neg`` ``(L, S, P, rows, N)`` and
    carries the scan's traced layer index here instead; every other path
    reads :meth:`at_layer`.
    """

    g_pos: jax.Array                 # (S, P, rows, N)
    g_neg: Optional[jax.Array]       # (S, P, rows, N) | None
    g_unit: Optional[jax.Array]      # (S, P, rows, 1) | None
    w_scale: jax.Array               # scalar quant scale
    k: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))
    layer: Optional[jax.Array] = None   # int32 index into stacked g_pos/g_neg

    def at_layer(self) -> "AnalogWeights":
        """These weights with ``layer``'s slice of each stacked array."""
        if self.layer is None:
            return self
        pick = lambda g: None if g is None else g[self.layer]
        return dataclasses.replace(self, g_pos=pick(self.g_pos),
                                   g_neg=pick(self.g_neg), layer=None)


def _partition(arr: jax.Array, k: int, p: int, rows: int) -> jax.Array:
    """(S, K, N) -> (S, P, rows, N), zero-padding K to P*rows."""
    s, _, n = arr.shape
    pad = p * rows - k
    if pad:
        arr = jnp.pad(arr, ((0, 0), (0, pad), (0, 0)))
    return arr.reshape(s, p, rows, n)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ProgrammedMatrix:
    """Deterministic half of :func:`program`: integer code stacks + scale.

    Everything here is independent of the trial PRNG key *and* of the
    On/Off ratio, so the sweep engine (``repro.sweep``) caches one
    ``ProgrammedMatrix`` per ``(mapping signature, weights hash)`` and
    amortizes quantize+map across all trials and all design points that
    share a compiled shape.
    """

    codes: ProgrammedCodes
    w_scale: jax.Array
    k: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))


def program_codes(w: jax.Array, spec: AnalogSpec) -> ProgrammedMatrix:
    """Quantize + map a float weight matrix ``(K, N)`` to integer codes."""
    if w.ndim != 2:
        raise ValueError(
            f"program_codes expects a 2-D (K, N) weight matrix, got shape "
            f"{w.shape}")
    k, n = w.shape
    m = spec.mapping
    mag_bits = None if m.scheme == "offset" else m.magnitude_bits
    qt = quantize_weights(w, m.weight_bits, magnitude_bits=mag_bits)
    pc = program_int_codes(qt.values.astype(jnp.int32), m)
    return ProgrammedMatrix(
        codes=pc, w_scale=qt.scale.astype(jnp.float32), k=k, n=n
    )


def program_from_codes(
    pm: ProgrammedMatrix,
    spec: AnalogSpec,
    key: Optional[jax.Array] = None,
) -> AnalogWeights:
    """Conductance-convert + partition + perturb cached code stacks.

    This is the per-trial half of :func:`program`; it is tracer-safe in
    ``spec.error.alpha`` and ``spec.mapping.on_off_ratio`` so vmapped
    trials and scalar-batched design points go through one compilation.
    """
    k, n = pm.k, pm.n
    pw = codes_to_weights(pm.codes, spec.mapping)

    p = spec.n_partitions(k)
    rows = spec.rows_per_partition(k)
    g_pos = _partition(pw.g_pos, k, p, rows)
    g_neg = _partition(pw.g_neg, k, p, rows) if pw.g_neg is not None else None
    g_unit = _partition(pw.g_unit, k, p, rows) if pw.g_unit is not None else None

    if spec.error.kind != "none" and key is not None:
        kp, kn, ku = jax.random.split(key, 3)
        g_pos = spec.error.perturb(g_pos, kp)
        g_neg = spec.error.perturb(g_neg, kn) if g_neg is not None else None
        g_unit = spec.error.perturb(g_unit, ku) if g_unit is not None else None

    if spec.aging_on and key is not None:
        g_pos, g_neg, g_unit = age_conductances(
            g_pos, g_neg, g_unit, spec, jax.random.fold_in(key, _AGE_FOLD))

    dt = spec.compute_dtype
    return AnalogWeights(
        g_pos=g_pos.astype(dt),
        g_neg=g_neg.astype(dt) if g_neg is not None else None,
        g_unit=g_unit.astype(dt) if g_unit is not None else None,
        w_scale=pm.w_scale,
        k=k,
        n=n,
    )


#: disjoint fold tag for aging keys — programming noise consumes ``key``
#: via ``split``, so folding keeps the two RNG streams independent and
#: leaves the error-model draws bit-identical when aging is off.
_AGE_FOLD = 0x616765  # "age"


def age_conductances(
    g_pos: jax.Array,
    g_neg: Optional[jax.Array],
    g_unit: Optional[jax.Array],
    spec: AnalogSpec,
    key: jax.Array,
    *,
    t_drift=None,
    t_fault=None,
) -> Tuple[jax.Array, Optional[jax.Array], Optional[jax.Array]]:
    """Apply ``spec.drift`` then ``spec.fault`` to a conductance stack.

    Drift decays the programmed (noise-perturbed) values; faults pin
    cells afterwards — a stuck cell reads its stuck value regardless of
    what was programmed into it.  ``t_drift``/``t_fault`` default to the
    spec's own evaluation ages (``spec.drift.t`` / ``spec.fault.t``);
    the serving-side healer overrides them per band
    (``repro.serve.health``: drift restarts at each reprogram, faults
    accumulate in absolute time).  At ``t = 1`` both passes are
    bit-identical no-ops.
    """
    td = spec.drift.t if t_drift is None else t_drift
    tf = spec.fault.t if t_fault is None else t_fault
    kd, kf = jax.random.split(key)
    gs = [g_pos, g_neg, g_unit]
    if spec.drift.kind != "none":
        gs = [spec.drift.apply(g, td, jax.random.fold_in(kd, i))
              if g is not None else None
              for i, g in enumerate(gs)]
    if spec.fault.kind != "none":
        g_lo = spec.mapping.g_min
        gs = [spec.fault.apply(g, tf, jax.random.fold_in(kf, i),
                               g_lo=g_lo, g_hi=1.0)
              if g is not None else None
              for i, g in enumerate(gs)]
    return gs[0], gs[1], gs[2]


def program(
    w: jax.Array,
    spec: AnalogSpec,
    key: Optional[jax.Array] = None,
) -> AnalogWeights:
    """Quantize + map + perturb a float weight matrix ``(K, N)``.

    Zero-padding rows added by partitioning are programmed at code 0 —
    with finite On/Off they still carry ``g_min`` and participate in the
    error/parasitic models, exactly like a real partially-used array.
    """
    return program_from_codes(program_codes(w, spec), spec, key)


def _apply_line(
    planes: jax.Array,   # (B, M, P, rows) signed bit planes
    g: jax.Array,        # (S, P, rows, N)
    spec: AnalogSpec,
) -> jax.Array:
    """Per-plane analog dot products -> (B, S, P, M, N)."""
    if not spec.parasitics_on:
        # One product per partition, each reading its (rows, N) array as
        # stored.  As one einsum batched over P, the TPU compiler lays the
        # weights out rows-minor for calibration's many rows and, inside a
        # layer scan, relays the whole stacked pack out before the loop: a
        # second copy of every conductance (7.3 GB at qwen3-14b's 4 layers).
        return jnp.stack([
            jnp.einsum("bmr,srn->bsmn", planes[:, :, i], g[:, i],
                       precision=jax.lax.Precision.HIGHEST)
            for i in range(planes.shape[2])], axis=2)
    b, m_, p, rows = planes.shape
    s, _, _, n = g.shape

    if spec.use_pallas:
        # Hot path: the Pallas Thomas-solve kernel, bit planes folded into
        # the kernel's independent-systems axis.  One call per (slice,
        # partition) via vmap (S and P are small static factors); the
        # dense lax.scan path below stays as the parity oracle.
        from repro.kernels import ops as kops

        xp = jnp.moveaxis(planes, 2, 0).reshape(p, b * m_, rows)

        def per_p(g_p, x_p):           # (rows, N), (B*M, rows)
            return kops.bitline_mvm(g_p, x_p, spec.r_hat)

        per_sp = jax.vmap(jax.vmap(per_p, in_axes=(0, 0)), in_axes=(0, None))
        out = per_sp(g, xp)            # (S, P, B*M, N)
        out = out.reshape(s, p, b, m_, n)
        return jnp.transpose(out, (2, 0, 1, 3, 4))       # (B, S, P, M, N)

    def one(plane_pk, g_pk):           # (M, rows), (rows, N)
        return parasitics.bitline_currents(g_pk, plane_pk, spec.r_hat)

    # vmap over slices, then partitions (axis 1 of planes), then input bits.
    over_p = jax.vmap(one, in_axes=(1, 0))   # (M,P,rows),(P,rows,N)->(P,M,N)
    over_sp = jax.vmap(lambda pl, gg: over_p(pl, gg), in_axes=(None, 0))
    over_bsp = jax.vmap(lambda pl, gg: over_sp(pl, gg), in_axes=(0, None))
    return over_bsp(planes, g)                           # (B, S, P, M, N)


def _maybe_pallas_fastpath(spec: AnalogSpec, collect: bool) -> bool:
    """Kernel-eligibility predicate for the differential calibrated chain.

    ``spec.fused != "off"`` selects the whole-chain fused serving kernels
    (``kernels.fused``): slice/partition-tiled, ADC + dequant in-kernel,
    both ``input_accum`` modes, and the Design-A parasitic variant (the
    per-bit Thomas solve inside the same launch).  Digital input
    accumulation under parasitics has no fused form — the parasitic
    kernel's switched-capacitor bit fold *is* analog accumulation — so
    that combination refuses here and falls back to the composed path,
    as does calibration collection and any non-differential or
    non-calibrated design.  Legacy ``use_pallas`` keeps its original,
    narrower domain (unsliced Design-A epilogue outside the kernel).
    """
    if (
        collect
        or spec.mapping.scheme != "differential"
        or spec.adc.style != "calibrated"
    ):
        return False
    if spec.fused != "off":
        return spec.input_accum == "analog" or not spec.parasitics_on
    return (
        spec.use_pallas
        and not spec.mapping.sliced
        and spec.input_accum == "analog"
    )


def reads_stacked(spec: AnalogSpec, aw: AnalogWeights, *,
                  collect: bool = False) -> bool:
    """Whether :func:`analog_matmul` hands this site's layer-stacked
    conductances whole to the fused Pallas kernel, which picks the layer
    itself (``AnalogWeights.layer``).  So it does where the call reaches
    the kernel (``fused="kernel"``, the fast path, linear or parasitic)
    and the kernel reads the stack as stored
    (``kernels.ops.fused_reads_stacked``: float32, ``rows % 8 == 0``,
    ``N`` whole lane tiles).  Calibration, the oracle and composed paths,
    other dtypes and 854-row arrays read a per-layer slice."""
    from repro.kernels import ops as kops

    return (spec.fused == "kernel"
            and _maybe_pallas_fastpath(spec, collect)
            and aw.g_pos.shape[-1] == aw.n
            and kops.fused_reads_stacked(aw.g_pos))


def fuse_signature(spec: AnalogSpec) -> Optional[Tuple]:
    """The static compile identity of a spec's fused serving kernel.

    Two fuse-eligible specs that agree on this tuple lower to the same
    fused Pallas program (the traced operands — conductances, calibrated
    ranges, scales, ``r_hat`` — carry everything else), so a profile
    compiles one fused kernel per distinct signature, not per site
    (``repro.hw.fused_site_classes``; pinned by the
    ``serve/fused-compile-per-site-class`` contract).  ``None``
    means the spec refuses to fuse (composed fallback).

    Only never-traced program-structure fields may appear here:
    mapping geometry (slice count / cell bits), ADC bit width, the
    input-accumulation mode (bit fold vs single dot), and whether the
    parasitic (Thomas-solve) kernel body is selected.
    """
    if spec.fused == "off" or not _maybe_pallas_fastpath(spec, False):
        return None
    m = spec.mapping
    n_bits = None if spec.input_accum == "analog" else spec.n_planes
    return (
        "parasitic" if spec.parasitics_on else "linear",
        m.n_slices, m.cell_bits, spec.adc.bits, n_bits,
        spec.n_planes if spec.parasitics_on else None,
    )


def analog_matmul(
    x: jax.Array,
    aw: AnalogWeights,
    spec: AnalogSpec,
    *,
    adc_lo: Optional[jax.Array] = None,   # (S,) calibrated per-slice limits
    adc_hi: Optional[jax.Array] = None,
    act_hi: Optional[jax.Array] = None,   # calibrated activation clip
    collect: bool = False,
):
    """Simulated analog ``x @ W`` for ``x`` of shape ``(..., K)``.

    Returns ``y`` of shape ``(..., N)``; with ``collect=True`` returns
    ``(y_ideal, stats)`` where ``stats`` is ``(S, 2)`` pre-ADC lo/hi
    percentiles for ADC range calibration (ADC bypassed).
    """
    m = spec.mapping
    lead = x.shape[:-1]
    k = x.shape[-1]
    if k != aw.k:
        raise ValueError(
            f"analog_matmul input depth {k} does not match the programmed "
            f"matrix depth {aw.k} (weights are ({aw.k}, {aw.n}))")
    xf = x.reshape(-1, k).astype(spec.compute_dtype)

    xq = quantize_acts(
        xf, spec.input_bits, signed=spec.signed_inputs, clip_hi=act_hi
    )
    p = spec.n_partitions(k)
    rows = spec.rows_per_partition(k)
    pad = p * rows - k
    x_int = xq.values
    if pad:
        x_int = jnp.pad(x_int, ((0, 0), (0, pad)))
    x_parts = x_int.reshape(-1, p, rows)

    lmax = m.levels_per_cell - 1
    gain = lmax / (1.0 - m.g_min)          # conductance -> code units
    slice_w = 2.0 ** (m.cell_bits * jnp.arange(m.n_slices, dtype=x.dtype))

    fast = _maybe_pallas_fastpath(spec, collect) and adc_lo is not None
    if not (fast and spec.fused != "off"):
        aw = aw.at_layer()                 # the fused wrappers take the index
    if fast:
        from repro.kernels import ops as kops

        if spec.fused != "off":
            # Whole-chain fused kernels: ADC epilogue, dequant and slice
            # accumulation inside the launch; one traced scale operand so
            # the sweep engine batches traced on_off_ratio (hence traced
            # gain) points through a single compilation.
            backend = "oracle" if spec.fused == "oracle" else "kernel"
            scale = gain * aw.w_scale * xq.scale
            if spec.parasitics_on:
                y = kops.fused_mvm_parasitic(
                    x_parts,
                    aw.g_pos[..., : aw.n], aw.g_neg[..., : aw.n],
                    r_hat=spec.r_hat, adc_lo=adc_lo, adc_hi=adc_hi,
                    adc_bits=spec.adc.bits, cell_bits=m.cell_bits,
                    n_bits=spec.n_planes, scale=scale, backend=backend,
                    layer=aw.layer,
                )
            else:
                n_bits = (None if spec.input_accum == "analog"
                          else spec.n_planes)
                y = kops.fused_mvm(
                    x_parts,
                    aw.g_pos[..., : aw.n], aw.g_neg[..., : aw.n],
                    adc_lo=adc_lo, adc_hi=adc_hi,
                    adc_bits=spec.adc.bits, cell_bits=m.cell_bits,
                    n_bits=n_bits, scale=scale, backend=backend,
                    layer=aw.layer,
                )
            return y.reshape(*lead, aw.n)

        if spec.parasitics_on:
            d_codes = kops.analog_mvm_parasitic(
                x_parts,
                aw.g_pos[..., : aw.n], aw.g_neg[..., : aw.n],
                r_hat=spec.r_hat, n_bits=spec.n_planes,
                adc_lo=adc_lo, adc_hi=adc_hi, adc_bits=spec.adc.bits,
                gain=gain,
            )
        else:
            d_codes = kops.analog_mvm(
                x_parts,
                aw.g_pos[..., : aw.n], aw.g_neg[..., : aw.n],
                adc_lo=adc_lo, adc_hi=adc_hi, adc_bits=spec.adc.bits,
                gain=gain,
            )
        y = d_codes * aw.w_scale * xq.scale
        return y.reshape(*lead, aw.n)

    if spec.input_accum == "analog" and not spec.parasitics_on:
        # Analog accumulation over input bits commutes with the dot product:
        # sum_b 2^b plane_b == x_int, so one matmul per (slice, partition).
        planes = x_parts[None]                               # (1, M, P, rows)
        bit_w = jnp.ones((1,), x.dtype)
    else:
        nb = spec.n_planes
        planes_flat = bit_planes(x_int, nb, signed=spec.signed_inputs)
        planes = planes_flat.reshape(nb, -1, p, rows)        # (B, M, P, rows)
        bit_w = 2.0 ** jnp.arange(nb, dtype=x.dtype)

    v_pos = _apply_line(planes, aw.g_pos, spec)              # (B, S, P, M, N)
    if m.scheme == "differential":
        v = v_pos - _apply_line(planes, aw.g_neg, spec)      # analog subtract
    else:
        v = v_pos
    if spec.input_accum == "analog" and spec.parasitics_on:
        # Parasitic solve is per input bit; analog accumulation happens in
        # the switched-capacitor stage after the bit-line, before the ADC.
        v = jnp.einsum("b,bspmn->spmn", bit_w, v)[None]
        bit_w = jnp.ones((1,), x.dtype)
        s_b = x_parts.sum(axis=-1)[None]                     # (1, M, P)
    else:
        s_b = planes.sum(axis=-1)                            # (B, M, P)

    if collect:
        stats = jnp.stack(
            [
                jnp.stack(adc_lib.range_from_samples(v[:, s]))
                for s in range(m.n_slices)
            ]
        )                                                     # (S, 2)
        v_hat = v
    elif spec.adc.style == "none":
        v_hat = v
    elif spec.adc.style == "fpg":
        bits = spec.fpg_adc_bits(k)
        lo, hi = adc_lib.fpg_range(
            rows,
            1.0,
            signed_inputs=spec.signed_inputs,
            differential=(m.scheme == "differential"),
        )
        if spec.input_accum == "analog":
            scale_in = float(2 ** (spec.input_bits - 1) - 1
                             if spec.signed_inputs else 2 ** spec.input_bits - 1)
            lo, hi = lo * scale_in, hi * scale_in
        # FPG means "a unique level per possible output": snap the ADC LSB
        # to the exact analog output grid (code spacing (1-g_min)/(L-1)).
        # Eq. (4) guarantees 2**bits levels cover the full range.
        grid = (1.0 - m.g_min) / lmax
        lo = grid * math.floor(lo / grid)
        hi = lo + (2 ** bits - 1) * grid
        v_hat = adc_lib.adc_quantize(v, lo, hi, bits)
    else:
        if adc_lo is None or adc_hi is None:
            raise ValueError(
                "adc.style='calibrated' requires adc_lo/adc_hi ranges from "
                "the calibration pass (analog_matmul(..., collect=True) or "
                "core.calibrate.calibrate_adc_for_matmul)")
        lo = jnp.reshape(adc_lo, (1, m.n_slices, 1, 1, 1)).astype(v.dtype)
        hi = jnp.reshape(adc_hi, (1, m.n_slices, 1, 1, 1)).astype(v.dtype)
        v_hat = adc_lib.adc_quantize(v, lo, hi, spec.adc.bits)

    # ---- digital aggregation + exact affine corrections -----------------
    if m.scheme == "differential":
        codes = v_hat * gain                                  # g_min cancels
        d = jnp.einsum("s,b,bspmn->mn", slice_w, bit_w, codes)
    else:
        if m.unit_column:
            vu = _apply_line(planes, aw.g_unit, spec)         # (B,S,P,M,1)
            if not collect and spec.adc.style != "none":
                if spec.adc.style == "fpg":
                    vu = adc_lib.adc_quantize(vu, lo, hi, bits)
                else:
                    vu = adc_lib.adc_quantize(vu, lo, hi, spec.adc.bits)
            # Unit column codes per slice sum to the offset: analog offset.
            codes = (v_hat - vu) * gain
            d = jnp.einsum("s,b,bspmn->mn", slice_w, bit_w, codes)
        else:
            # g_min floor correction uses the exact digital sum of input
            # bits per partition (the same digital sum the offset needs).
            s_bp = jnp.swapaxes(s_b, 1, 2)        # (B, M, P) -> (B, P, M)
            codes = (v_hat - m.g_min * s_bp[:, None, :, :, None]) * gain
            d = jnp.einsum("s,b,bspmn->mn", slice_w, bit_w, codes)
            x_sum = xq.values.sum(axis=-1)                    # (M,)
            d = d - m.offset_code * x_sum[:, None]

    y = d * aw.w_scale * xq.scale
    y = y.reshape(*lead, aw.n)
    if collect:
        return y, stats
    return y


def ideal_matmul_int(x: jax.Array, aw: AnalogWeights, spec: AnalogSpec,
                     act_hi: Optional[jax.Array] = None) -> jax.Array:
    """Reference: the same quantization pipeline with a perfect analog core
    (no errors, no ADC).  Used for SNR measurements (Eq. 9/10)."""
    err_free = dataclasses.replace(
        spec, error=ErrorModel(), adc=adc_lib.ADCConfig(style="none"),
        r_hat=0.0, use_pallas=False, fused="off",
    )
    return analog_matmul(x, aw, err_free, act_hi=act_hi)
