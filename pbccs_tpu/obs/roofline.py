"""Device cost-model & roofline attribution plane.

The repo's op-count bounds were hand-written constants
(bench.py:_estimate_flops, the old "~3 ms VPU bound" comment in
ops/dense_score_pallas.py); nothing live knew what a compiled bucket
*should* cost or how close each dispatch came.  This module makes
achieved-vs-bound (the SURVEY section-7 / docs/PROFILE_r06.md framing)
a continuously measured, regression-defended quantity:

  * CostCard -- per shape-bucket cost bound extracted from XLA itself
    via the AOT path (``lowered.compile().cost_analysis()`` /
    ``memory_analysis()``): flops, bytes accessed, peak HBM, arithmetic
    intensity.  Extraction lowers the SAME canonical program the bucket
    runs (parallel/batch._batch_setup at the polisher's exact
    shapes/statics).  It is a second tracing, lowering and compile of
    that program (the compile a disk hit where the persistent cache
    holds it), so it runs where a card is asked for -- `ccs warmup`
    mints one for each bucket of its menu -- and never on the polish
    path: a server charges against the cards an earlier `ccs warmup`
    left in the store (it loads them as it starts), a batch run against
    none.  Cards are
    cached beside the compile cache (roofline_cards.json, or
    PBCCS_ROOFLINE_CARDS=PATH) with no timestamps, so the file is
    byte-deterministic for a given jax build -- the property
    tools/roofline_smoke.py enforces in tier-1.
  * Charging -- every execution of the canonical program
    (BatchPolisher._setup) charges card.flops * Z // card.z to
    per-bucket counters (integer math: deterministic), and refine-level
    + dispatch-level scopes attribute wall and device-wait seconds.
  * Gauges -- achieved TFLOP/s, efficiency-vs-peak and kernel_fraction
    per bucket plus fleet-level aggregates, registered in the obs
    registry and therefore federated through --metricsPort, surfaced in
    the status verb (serve/protocol.py FIELD_ROOFLINE), `ccs top`, the
    perf ledger (roofline_* fields, see obs/ledger.py) and the
    `ccs roofline` report below.

Degradation contract: every extraction/persistence failure yields an
absent card and a debug log line, never an exception on the polish
path.  PBCCS_ROOFLINE=0 disables the whole plane.

Achieved TFLOP/s is flops-charged / refine WALL seconds -- a lower
bound on device rate (conservative by construction); kernel_fraction
(device-wait / wall) says how much of the gap is host overhead.
Efficiency divides by the published peak of the TPU `device_kind` that
ran (TPU_PEAK_TFLOPS; a kind with no entry yields NO efficiency figure),
a nominal ceiling off-TPU (HOST_PEAK_TFLOPS), or
PBCCS_ROOFLINE_PEAK_TFLOPS where set.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from dataclasses import asdict, dataclass

from pbccs_tpu.obs import metrics as _metrics

ROOFLINE_SCHEMA_VERSION = 1
CARDS_BASENAME = "roofline_cards.json"

# Efficiency denominators (TFLOP/s); PBCCS_ROOFLINE_PEAK_TFLOPS overrides
# them for calibrated fleets.  On a TPU the peak is the published
# per-chip bf16 figure of the `device_kind` jax reports -- a kind that is
# not in the table gets no efficiency figure, never another chip's peak.
TPU_PEAK_TFLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197.0,
}
# Off-TPU the ceilings are deliberately coarse (the defended metric is
# the trend, not the absolute).
HOST_PEAK_TFLOPS = {
    "gpu": 60.0,
    "cpu": 0.1,     # ~one AVX2 core's worth; CI runs are single-core
}

# metric names (REG001 drift-checks these against docs/DESIGN.md)
BOUND_FLOPS = "ccs_roofline_bound_flops"
BOUND_BYTES = "ccs_roofline_bound_bytes"
BOUND_INTENSITY = "ccs_roofline_intensity"
FLOPS_TOTAL = "ccs_roofline_flops_total"
BYTES_TOTAL = "ccs_roofline_bytes_total"
REFINE_SECONDS = "ccs_roofline_refine_seconds_total"
DEVICE_SECONDS = "ccs_roofline_device_seconds_total"
DISPATCHES = "ccs_roofline_dispatches_total"
DISPATCH_SECONDS = "ccs_roofline_dispatch_seconds_total"
DISPATCH_DEVICE_SECONDS = "ccs_roofline_dispatch_device_seconds_total"
ACHIEVED_TFLOPS = "ccs_roofline_achieved_tflops"
EFFICIENCY = "ccs_roofline_efficiency"
KERNEL_FRACTION = "ccs_roofline_kernel_fraction"
ACHIEVED_OVERALL = "ccs_roofline_achieved_tflops_overall"
EFFICIENCY_OVERALL = "ccs_roofline_efficiency_overall"


def enabled() -> bool:
    return os.environ.get("PBCCS_ROOFLINE", "1") != "0"


def _sig(v: float) -> float:
    """6 significant figures (NOT decimal places: CPU achieved-TFLOP/s
    values live around 1e-7 and must not round to zero)."""
    return float(f"{v:.6g}") if v else 0.0


def bucket_label(imax: int, jmax: int, r: int) -> str:
    """Human-stable label for a resources.shape_bucket (Z excluded --
    the card normalizes per ZMW slot)."""
    return f"I{int(imax)}xJ{int(jmax)}xR{int(r)}"


def label_from_capacity_bucket(bucket) -> str | None:
    """('shape', imax, jmax, r) -> label, else None."""
    try:
        kind, imax, jmax, r = bucket
    except (TypeError, ValueError):
        return None
    if kind != "shape":
        return None
    return bucket_label(imax, jmax, r)


@dataclass(frozen=True)
class CostCard:
    """XLA-derived cost bound for one canonical bucket program.

    flops / bytes_accessed / peak_hbm_bytes are for ONE execution of
    _batch_setup at the extraction geometry (z slots); charge for a
    dispatch at Z slots with ``flops * Z // z`` (integer: deterministic).
    """
    label: str
    imax: int
    jmax: int
    r: int
    z: int
    width: int
    flops: int
    bytes_accessed: int
    peak_hbm_bytes: int
    intensity: float | None
    optimal_seconds: float | None
    platform: str
    jax_version: str
    schema_version: int = ROOFLINE_SCHEMA_VERSION

    def flops_for(self, z: int) -> int:
        return self.flops * int(z) // max(1, self.z)

    def bytes_for(self, z: int) -> int:
        return self.bytes_accessed * int(z) // max(1, self.z)


# ------------------------------------------------------------ extraction

def card_from_compiled(compiled, *, label: str, imax: int, jmax: int,
                       r: int, z: int, width: int) -> CostCard | None:
    """Build a CostCard from a jax Compiled object's analyses.  Returns
    None (absent card) on ANY shortfall -- missing/odd cost_analysis,
    raising backends -- never raises."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    flops = ca.get("flops")
    if not isinstance(flops, (int, float)) or flops <= 0:
        return None
    flops = int(flops)
    raw_bytes = ca.get("bytes accessed")
    nbytes = int(raw_bytes) if isinstance(raw_bytes, (int, float)) \
        and raw_bytes > 0 else 0
    raw_opt = ca.get("optimal_seconds")
    optimal = float(raw_opt) if isinstance(raw_opt, (int, float)) \
        and raw_opt > 0 else None
    peak_hbm = 0
    try:
        mem = compiled.memory_analysis()
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "alias_size_in_bytes"):
            v = getattr(mem, attr, None)
            if isinstance(v, (int, float)) and v > 0:
                peak_hbm += int(v)
    except Exception:
        peak_hbm = 0
    intensity = round(flops / nbytes, 6) if nbytes > 0 else None
    try:
        import jax
        platform = jax.default_backend()
        jax_version = jax.__version__
    except Exception:
        platform, jax_version = "unknown", "unknown"
    return CostCard(label=label, imax=int(imax), jmax=int(jmax),
                    r=int(r), z=int(z), width=int(width), flops=flops,
                    bytes_accessed=nbytes, peak_hbm_bytes=peak_hbm,
                    intensity=intensity, optimal_seconds=optimal,
                    platform=platform, jax_version=jax_version)


def extract_card(*, imax: int, jmax: int, r: int, z: int, width: int,
                 use_pallas: bool, guided_passes: int) -> CostCard | None:
    """Lower + AOT-compile the canonical bucket program at the given
    geometry and read XLA's cost model.  The program and statics mirror
    BatchPolisher._setup exactly, so the persistent compile cache makes
    the AOT compile a disk hit when the JIT path just ran."""
    try:
        import jax
        import jax.numpy as jnp

        from pbccs_tpu.parallel import batch as _batch
        from pbccs_tpu.runtime.cache import suppress_cache_metrics

        s = jax.ShapeDtypeStruct
        z, r, imax, jmax = int(z), int(r), int(imax), int(jmax)
        lowered = _batch.lowering_target().lower(
            s((z, jmax), jnp.int8),        # template tracks
            s((z,), jnp.int32),            # template lengths
            s((z, 8, 4), jnp.float32),     # host transition tables
            s((z, r, imax), jnp.int8),     # reads
            s((z, r), jnp.int32),          # rlens
            s((z, r), jnp.int32),          # strands
            s((z, r), jnp.int32),          # tstarts
            s((z, r), jnp.int32),          # tends
            int(width),
            use_pallas=bool(use_pallas), mesh=None,
            guided_passes=int(guided_passes))
        # the AOT compile's cache hit/miss must not reach the ledger's
        # deterministic compile counters (it races the workload's jit)
        with suppress_cache_metrics():
            compiled = lowered.compile()
    except Exception:
        return None
    return card_from_compiled(compiled, label=bucket_label(imax, jmax, r),
                              imax=imax, jmax=jmax, r=r, z=z, width=width)


# ----------------------------------------------------------- persistence

def cards_path() -> str | None:
    """Where the card cache lives: PBCCS_ROOFLINE_CARDS wins, else
    beside the persistent compile cache; None when neither is set
    (cards stay in-memory only)."""
    explicit = os.environ.get("PBCCS_ROOFLINE_CARDS")
    if explicit:
        return explicit
    try:
        import jax
        cache_dir = jax.config.jax_compilation_cache_dir
    except Exception:
        cache_dir = None
    if not cache_dir:
        return None
    return os.path.join(cache_dir, CARDS_BASENAME)


def cards_to_doc(cards: dict[str, CostCard]) -> str:
    """Canonical serialized form -- sorted keys, no timestamps, so two
    identical extractions produce byte-identical files."""
    doc = {"schema_version": ROOFLINE_SCHEMA_VERSION,
           "cards": {label: asdict(card)
                     for label, card in sorted(cards.items())}}
    return json.dumps(doc, sort_keys=True, indent=1) + "\n"


def load_cards(path: str) -> dict[str, CostCard]:
    """Best-effort load; unreadable/alien files yield {}."""
    out: dict[str, CostCard] = {}
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("schema_version") != ROOFLINE_SCHEMA_VERSION:
            return {}
        for label, raw in (doc.get("cards") or {}).items():
            try:
                out[label] = CostCard(**raw)
            except TypeError:
                continue
    except Exception:
        return {}
    return out


def save_cards(path: str, cards: dict[str, CostCard]) -> bool:
    """Merge-and-write (atomic).  Swallows IO errors: persistence is an
    optimization, never a polish-path failure."""
    try:
        from pbccs_tpu.resilience.resources import atomic_output
        merged = load_cards(path)
        merged.update(cards)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with atomic_output(path, "roofline_cards") as f:
            f.write(cards_to_doc(merged))
        return True
    except Exception:
        return False


# ------------------------------------------------------------- the plane

class _Bucket:
    """Cumulative per-bucket attribution (process-local)."""

    __slots__ = ("card", "flops", "bytes", "refine_s", "device_s",
                 "dispatches", "dispatch_s", "dispatch_device_s")

    def __init__(self):
        self.card: CostCard | None = None
        self.flops = 0
        self.bytes = 0
        self.refine_s = 0.0
        self.device_s = 0.0
        self.dispatches = 0
        self.dispatch_s = 0.0
        self.dispatch_device_s = 0.0


class RooflineTracker:
    """Process-wide card store + charge/measure surface behind the
    module-level helpers.  All mutation under one lock; the hot charge
    path is a dict hit + a few adds."""

    def __init__(self, registry: _metrics.MetricsRegistry | None = None):
        self._registry = registry or _metrics.default_registry()
        self._lock = threading.Lock()
        self._buckets: dict[str, _Bucket] = {}
        self._loaded_from: str | None = None
        self._peak: float | None = None
        self._peak_resolved = False

    # -- cards ---------------------------------------------------------

    def _bucket(self, label: str) -> _Bucket:
        b = self._buckets.get(label)
        if b is None:
            b = self._buckets[label] = _Bucket()
        return b

    def register_card(self, card: CostCard, *, persist: bool = True) -> None:
        with self._lock:
            self._bucket(card.label).card = card
        gauge = self._registry.gauge
        gauge(BOUND_FLOPS, "XLA cost-model flops for one canonical bucket "
          "program (CostCard bound)", bucket=card.label).set(card.flops)
        gauge(BOUND_BYTES, "XLA cost-model bytes accessed per canonical "
          "bucket program", bucket=card.label).set(card.bytes_accessed)
        if card.intensity is not None:
            gauge(BOUND_INTENSITY, "Arithmetic intensity (flops/byte) of "
              "the bucket program", bucket=card.label).set(card.intensity)
        if persist:
            path = cards_path()
            if path:
                save_cards(path, {card.label: card})

    def card(self, label: str) -> CostCard | None:
        with self._lock:
            b = self._buckets.get(label)
            return b.card if b else None

    def load_persisted(self) -> int:
        """Pick up cards minted by earlier processes (warmup) --
        idempotent, best-effort."""
        path = cards_path()
        with self._lock:
            if not path or path == self._loaded_from:
                return 0
            self._loaded_from = path
        cards = load_cards(path)
        for card in cards.values():
            self.register_card(card, persist=False)
        return len(cards)

    def ensure_card(self, *, imax: int, jmax: int, r: int, z: int,
                    width: int, use_pallas: bool,
                    guided_passes: int) -> CostCard | None:
        """Memoized per-bucket extraction: disk cards first, then one
        AOT extraction per process.  Never raises."""
        if not enabled():
            return None
        label = bucket_label(imax, jmax, r)
        with self._lock:
            b = self._buckets.get(label)
            if b is not None and b.card is not None:
                return b.card
        self.load_persisted()
        with self._lock:
            b = self._buckets.get(label)
            if b is not None and b.card is not None:
                return b.card
        card = extract_card(imax=imax, jmax=jmax, r=r, z=z, width=width,
                            use_pallas=use_pallas,
                            guided_passes=guided_passes)
        if card is not None:
            self.register_card(card)
        return card

    # -- charging ------------------------------------------------------

    def charge_execution(self, *, imax: int, jmax: int, r: int,
                         z: int) -> None:
        """One execution of the canonical program at Z slots: charge the
        bound (integer-scaled from the card)."""
        if not enabled():
            return
        label = bucket_label(imax, jmax, r)
        with self._lock:
            b = self._buckets.get(label)
            card = b.card if b else None
            if card is None:
                return
            flops = card.flops_for(z)
            nbytes = card.bytes_for(z)
            b.flops += flops
            b.bytes += nbytes
        counter = self._registry.counter
        counter(FLOPS_TOTAL, "CostCard-bound flops charged for executed "
          "canonical bucket programs", bucket=label).inc(flops)
        counter(BYTES_TOTAL, "CostCard-bound bytes charged for executed "
          "canonical bucket programs", bucket=label).inc(nbytes)

    @contextlib.contextmanager
    def refine_scope(self, *, imax: int, jmax: int, r: int):
        """Measure one refine pass: wall + device-wait seconds, then
        refresh the per-bucket achieved/efficiency/kernel gauges."""
        if not enabled():
            yield
            return
        from pbccs_tpu.runtime import timing
        label = bucket_label(imax, jmax, r)
        win = timing.window()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            wall = time.perf_counter() - t0
            dev = timing.device_wait_seconds(win)
            with self._lock:
                b = self._bucket(label)
                b.refine_s += wall
                b.device_s += dev
            counter = self._registry.counter
            counter(REFINE_SECONDS, "Wall seconds inside refine passes, per "
              "bucket", bucket=label).inc(wall)
            counter(DEVICE_SECONDS, "Device-wait seconds attributed to refine "
              "passes, per bucket", bucket=label).inc(dev)
            self._refresh_gauges(label)

    _dispatch_depth = threading.local()

    @contextlib.contextmanager
    def dispatch_scope(self, label: str | None, *, zmws: int = 0):
        """Per-dispatch device-timing scope (pool workers + serve
        engine).  Reentrancy-guarded: fleet serve runs _run_polish inside
        a pool task; only the OUTERMOST scope counts."""
        depth = getattr(self._dispatch_depth, "v", 0)
        if not enabled() or label is None or depth > 0:
            yield
            return
        from pbccs_tpu.runtime import timing
        self._dispatch_depth.v = depth + 1
        win = timing.window()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self._dispatch_depth.v = depth
            wall = time.perf_counter() - t0
            dev = timing.device_wait_seconds(win)
            with self._lock:
                b = self._bucket(label)
                b.dispatches += 1
                b.dispatch_s += wall
                b.dispatch_device_s += dev
            counter = self._registry.counter
            counter(DISPATCHES, "Device dispatches measured by the roofline "
              "plane, per bucket", bucket=label).inc()
            counter(DISPATCH_SECONDS, "Wall seconds inside measured "
              "dispatches, per bucket", bucket=label).inc(wall)
            counter(DISPATCH_DEVICE_SECONDS, "Device-wait seconds inside "
              "measured dispatches, per bucket", bucket=label).inc(dev)
            self._refresh_gauges(label)

    # -- derived gauges / reporting -----------------------------------

    def peak_tflops(self) -> float | None:
        """The efficiency denominator, or None when this device has no
        known peak (logged once): efficiency is then not reported."""
        with self._lock:
            if self._peak_resolved:
                return self._peak
        peak = None
        env = os.environ.get("PBCCS_ROOFLINE_PEAK_TFLOPS")
        if env:
            try:
                peak = float(env)
            except ValueError:
                peak = None
        if peak is None:
            import jax

            from pbccs_tpu.runtime.logging import Logger

            try:
                dev = jax.devices()[0]
                table, key = ((TPU_PEAK_TFLOPS, dev.device_kind)
                              if dev.platform == "tpu"
                              else (HOST_PEAK_TFLOPS, dev.platform))
                peak = table.get(key)
                why = f"no published peak for device {key!r}"
            except RuntimeError as e:
                why = f"jax could not be asked for its device ({e})"
            if peak is None:
                Logger.default().warn(
                    f"roofline: {why}; no efficiency figure is reported "
                    "(set PBCCS_ROOFLINE_PEAK_TFLOPS to supply one)")
        with self._lock:
            self._peak, self._peak_resolved = peak, True
            return self._peak

    def _refresh_gauges(self, label: str) -> None:
        peak = self.peak_tflops()
        with self._lock:
            b = self._buckets.get(label)
            if b is None:
                return
            achieved = (b.flops / 1e12 / b.refine_s) if b.refine_s > 0 \
                else 0.0
            kfrac = (b.dispatch_device_s / b.dispatch_s) \
                if b.dispatch_s > 0 else (
                    b.device_s / b.refine_s if b.refine_s > 0 else 0.0)
            tot_flops = sum(x.flops for x in self._buckets.values())
            tot_wall = sum(x.refine_s for x in self._buckets.values())
        gauge = self._registry.gauge
        gauge(ACHIEVED_TFLOPS, "Achieved TFLOP/s vs the CostCard bound "
          "(flops charged / refine wall; a lower bound on device rate)",
          bucket=label).set(_sig(achieved))
        if peak:
            gauge(EFFICIENCY, "Achieved TFLOP/s over the device's peak",
              bucket=label).set(_sig(achieved / peak))
        gauge(KERNEL_FRACTION, "Device-wait share of measured wall per "
          "bucket (roofline plane)", bucket=label).set(_sig(kfrac))
        overall = (tot_flops / 1e12 / tot_wall) if tot_wall > 0 else 0.0
        gauge(ACHIEVED_OVERALL, "Achieved TFLOP/s across all buckets "
          "(roofline plane)").set(_sig(overall))
        if peak:
            gauge(EFFICIENCY_OVERALL, "Fleet-level achieved/peak efficiency "
              "(roofline plane)").set(_sig(overall / peak))

    def status_block(self) -> dict | None:
        """The status-verb `roofline` block (serve/protocol.py
        FIELD_ROOFLINE); None when the plane has nothing to report."""
        with self._lock:
            if not self._buckets:
                return None
            buckets = {}
            for label, b in sorted(self._buckets.items()):
                entry: dict = {}
                if b.card is not None:
                    entry.update(flops=b.card.flops,
                                 bytes=b.card.bytes_accessed,
                                 intensity=b.card.intensity,
                                 card_z=b.card.z)
                achieved = (b.flops / 1e12 / b.refine_s) \
                    if b.refine_s > 0 else 0.0
                entry.update(
                    flops_charged=b.flops,
                    refine_s=round(b.refine_s, 4),
                    device_s=round(b.device_s, 4),
                    dispatches=b.dispatches,
                    dispatch_s=round(b.dispatch_s, 4),
                    achieved_tflops=_sig(achieved))
                buckets[label] = entry
        peak = self.peak_tflops()
        for entry in buckets.values():
            a = entry.get("achieved_tflops", 0.0)
            entry["efficiency"] = _sig(a / peak) if peak else None
        return {"schema_version": ROOFLINE_SCHEMA_VERSION,
                "peak_tflops": peak, "buckets": buckets}

    def reset_for_tests(self) -> None:
        with self._lock:
            self._buckets.clear()
            self._loaded_from = None
            self._peak, self._peak_resolved = None, False


_tracker = RooflineTracker()


def tracker() -> RooflineTracker:
    return _tracker


# convenience passthroughs used on the polish/dispatch paths
def note_bucket(**kw) -> CostCard | None:
    return _tracker.ensure_card(**kw)


def charge_execution(**kw) -> None:
    _tracker.charge_execution(**kw)


def refine_scope(**kw):
    return _tracker.refine_scope(**kw)


def dispatch_scope(label, **kw):
    return _tracker.dispatch_scope(label, **kw)


# -------------------------------------------------------- ccs roofline

def _rows_from_block(block: dict) -> list[dict]:
    peak = block.get("peak_tflops")
    rows = []
    for label, e in sorted((block.get("buckets") or {}).items()):
        rows.append({"bucket": label, "flops": e.get("flops"),
                     "bytes": e.get("bytes"),
                     "intensity": e.get("intensity"),
                     "dispatches": e.get("dispatches", 0),
                     "refine_s": e.get("refine_s", 0.0),
                     "achieved_tflops": e.get("achieved_tflops", 0.0),
                     "efficiency": e.get("efficiency", 0.0),
                     "peak_tflops": peak})
    return rows


def _rows_from_cards(cards: dict[str, CostCard]) -> list[dict]:
    rows = []
    for label, c in sorted(cards.items()):
        rows.append({"bucket": label, "flops": c.flops,
                     "bytes": c.bytes_accessed, "intensity": c.intensity,
                     "card_z": c.z, "width": c.width,
                     "peak_hbm_bytes": c.peak_hbm_bytes,
                     "platform": c.platform,
                     "jax_version": c.jax_version})
    return rows


def _fmt_num(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.4g}"
    if isinstance(v, int) and abs(v) >= 10_000:
        return f"{v:.3e}"
    return str(v)


def render_rows_text(rows: list[dict]) -> str:
    if not rows:
        return "(no roofline data)"
    cols = ["bucket", "flops", "bytes", "intensity", "dispatches",
            "refine_s", "achieved_tflops", "efficiency"]
    cols = [c for c in cols if any(c in r for r in rows)]
    table = [[_fmt_num(r.get(c)) for c in cols] for r in rows]
    widths = [max(len(c.upper()), *(len(row[i]) for row in table))
              for i, c in enumerate(cols)]
    out = ["  ".join(c.upper().ljust(w) for c, w in zip(cols, widths))]
    for row in table:
        out.append("  ".join(v.ljust(w) for v, w in zip(row, widths)))
    return "\n".join(out)


def _block_from_ledger(path: str) -> dict | None:
    """Synthesize a report block from the LAST ledger record carrying
    roofline fields (batch runs)."""
    from pbccs_tpu.obs.ledger import read_ledger
    records, _ = read_ledger(path)
    rec = next((r for r in reversed(records)
                if r.get("roofline_flops")), None)
    if rec is None:
        return None
    return {"schema_version": ROOFLINE_SCHEMA_VERSION,
            "peak_tflops": None,
            "buckets": {"(run total)": {
                "flops": rec.get("roofline_flops"),
                "bytes": rec.get("roofline_bytes"),
                "achieved_tflops": rec.get("roofline_achieved_tflops"),
                "efficiency": rec.get("roofline_efficiency"),
                "dispatches": rec.get("polish_dispatches")}}}


def _block_from_target(target: str, timeout: float) -> dict:
    from pbccs_tpu.serve.client import CcsClient
    host, _, port = target.rpartition(":")
    with CcsClient(host or "127.0.0.1", int(port),
                   timeout=timeout) as client:
        status = client.status(timeout=timeout)
    block = status.get("roofline")
    if not block:
        raise SystemExit(
            f"ccs roofline: {target} reports no roofline block (no "
            "warmed buckets yet, or PBCCS_ROOFLINE=0 on the replica)")
    return block


def run_roofline(argv: list[str] | None = None) -> int:
    """`ccs roofline`: per-bucket bound/measured/efficiency report for a
    live fleet (--target status verb), a batch run (--ledger), or the
    card cache itself (--cards / beside the compile cache)."""
    import argparse
    p = argparse.ArgumentParser(
        prog="ccs roofline",
        description="Render the per-bucket roofline table: XLA CostCard "
                    "bound, measured device time, achieved TFLOP/s and "
                    "efficiency-vs-peak.")
    p.add_argument("--target", metavar="HOST:PORT", default=None,
                   help="Live serve/router replica: read the status-verb "
                        "roofline block.")
    p.add_argument("--ledger", metavar="PATH", default=None,
                   help="Perf-ledger NDJSON: summarize the last record "
                        "carrying roofline_* fields (batch runs).")
    p.add_argument("--cards", metavar="PATH", default=None,
                   help="CostCard cache file (default: "
                        "PBCCS_ROOFLINE_CARDS, else roofline_cards.json "
                        "beside the persistent compile cache).")
    p.add_argument("--format", choices=("text", "json"), default="text")
    p.add_argument("--timeout", type=float, default=5.0)
    args = p.parse_args(argv)

    doc: dict = {"schema_version": ROOFLINE_SCHEMA_VERSION}
    if args.target:
        block = _block_from_target(args.target, args.timeout)
        doc.update(source="status", target=args.target, block=block,
                   rows=_rows_from_block(block))
    elif args.ledger:
        block = _block_from_ledger(args.ledger)
        if block is None:
            raise SystemExit(f"ccs roofline: {args.ledger} has no "
                             "record with roofline fields")
        doc.update(source="ledger", ledger=args.ledger, block=block,
                   rows=_rows_from_block(block))
    else:
        path = args.cards or cards_path()
        if not path:
            raise SystemExit(
                "ccs roofline: no card source -- pass --cards/--target/"
                "--ledger or set PBCCS_ROOFLINE_CARDS / a compile cache "
                "dir")
        cards = load_cards(path)
        if not cards:
            raise SystemExit(f"ccs roofline: no cards at {path} (run "
                             "`ccs warmup` with the bucket menu first)")
        doc.update(source="cards", cards_path=path,
                   rows=_rows_from_cards(cards))

    if args.format == "json":
        print(json.dumps(doc, sort_keys=True))
    else:
        print(render_rows_text(doc["rows"]))
    return 0
