"""Projection-health telemetry: numerics observability for the COAP math.

The tracer (``obs/trace.py``) and registry (``obs/registry.py``) see
wall-clock and counters; this module watches whether the projection is
silently degrading training. Per-bucket metrics come from two channels:

* **Refresh boundaries** (device-side, inside the optimizer's jitted
  update): the full gradient ``G`` is already materialized in the refresh
  branch, so captured energy ``‖G·P‖²_F/‖G‖²_F``, the Eqn-6 objective
  residual ``‖G − G P Pᵀ‖²_F/‖G‖²_F`` (via the trace identity — no m×n
  intermediate), and the subspace-drift proxy ``‖P̂_oldᵀP̂_new‖²_F/r``
  (column-normalized) cost only a few extra reductions. The emit lives
  under the same ``lax.cond`` as the refresh itself and ships scalars
  through ``jax.debug.callback`` — non-refresh steps execute NOTHING, so
  enabling health telemetry adds exactly zero extra HBM round-trips of
  ``G`` outside refresh steps (certified by ``BENCH_obs.json``'s
  ``health`` block).
* **Sampled step cadence** (host-side, :func:`observe_state`): int8
  moment-codec saturation/scale health and relative quant error, plus the
  ``sync_codes`` EF-sidecar norm trajectory, computed from the OPTIMIZER
  STATE alone — structurally no gradient access.

Rows append to a ``health.jsonl`` journal next to the trace (same
torn-write-tolerant format), and every metric mirrors into the process
registry as a ``health/<bucket>/<metric>`` gauge so it rides heartbeats
and dryrun artifacts for free. :func:`analyze` turns a journal into typed
verdicts (RANK_STARVED, QUANT_SATURATED, EF_NOT_DRAINING,
SUBSPACE_THRASH) that ``launch/fleet_status`` renders per host and
``plan/solver.solve(health_report=...)`` feeds back into rank floors.

Like its siblings this module imports ONLY the stdlib at module scope —
``launch/fleet_status`` must stay importable on an operator box without
jax. The device-side emitters import jax lazily, inside the traced
functions that are already jax-bound.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics
import threading
import time
from typing import Any, Dict, List, Optional

HEALTH_CODEC_V1 = "coap-health/v1"

# Host-side sampling cadence (steps between observe_state calls); refresh
# metrics follow the optimizer's own T_u schedule and need no knob.
DEFAULT_SAMPLE_EVERY = 25

VERDICT_RANK_STARVED = "RANK_STARVED"
VERDICT_QUANT_SATURATED = "QUANT_SATURATED"
VERDICT_EF_NOT_DRAINING = "EF_NOT_DRAINING"
VERDICT_SUBSPACE_THRASH = "SUBSPACE_THRASH"
KNOWN_VERDICTS = (
    VERDICT_RANK_STARVED,
    VERDICT_QUANT_SATURATED,
    VERDICT_EF_NOT_DRAINING,
    VERDICT_SUBSPACE_THRASH,
)

DEFAULT_THRESHOLDS: Dict[str, float] = {
    # Median captured energy below this after warmup -> the rank floor is
    # starving the subspace (GaLore's quality-tracks-energy observation).
    "energy_floor": 0.5,
    # Median energy at/above this with no other verdicts -> headroom: the
    # solver may relax the bucket's rank floor one pow2 step.
    "energy_headroom": 0.98,
    # Column-normalized cross-refresh overlap below this after warmup ->
    # the subspace is thrashing (every refresh lands somewhere new).
    "overlap_floor": 0.5,
    # Refreshes to skip before drift/energy judgments (init + settle).
    "warmup_refreshes": 2,
    # |q| == 127 rail fraction above this -> codec saturating (absmax
    # scaling puts ~1/256 of uniform mass on the rail; a spike means the
    # distribution collapsed onto it). Non-finite scales always fire.
    "sat_rate_max": 0.05,
    # EF rms last-third/first-third growth ratio above this -> the error
    # feedback is accumulating instead of draining ~1/T.
    "ef_growth_max": 3.0,
    # Minimum EF samples before the growth-ratio judgment.
    "ef_min_samples": 6,
}


def bucket_label(kind: str, shape, dtype) -> str:
    """The stable per-bucket health key: ``<kind>:<dims>x..:<dtype>`` —
    deliberately WITHOUT the rank, so a recorded journal still addresses
    the same bucket after the solver tightens/relaxes its rank floor."""
    dims = "x".join(str(int(s)) for s in shape)
    return f"{kind}:{dims}:{dtype}"


# ---------------------------------------------------------------------------
# Monitor: journal writer + registry mirror
# ---------------------------------------------------------------------------
class HealthMonitor:
    """Appends health rows to one jsonl journal (torn-write-tolerant, like
    ``trace.jsonl``) and mirrors every metric into the process registry as
    a ``health/<bucket>/<metric>`` gauge. ``path=None`` disables: the
    device-side emitters check :attr:`enabled` at trace time, so disabled
    runs compile bit-identical programs."""

    def __init__(self, path: Optional[str] = None,
                 host: Optional[str] = None,
                 sample_every: int = DEFAULT_SAMPLE_EVERY):
        self.path = path
        self.host = host or os.environ.get("REPRO_HOST_ID", "")
        self.sample_every = int(sample_every)
        self._lock = threading.Lock()
        self._f = None
        if path:
            parent = os.path.dirname(path)
            if parent:
                os.makedirs(parent, exist_ok=True)
            self._f = open(path, "a")

    @property
    def enabled(self) -> bool:
        return self._f is not None

    def record(self, step: int, bucket: str, event: str,
               metrics: Dict[str, float]) -> None:
        """One journal row + registry gauges. ``event`` is ``"refresh"``
        (device emit at a refresh boundary) or ``"sample"`` (host-side
        state observation)."""
        if self._f is None:
            return
        clean: Dict[str, float] = {}
        for k, v in metrics.items():
            try:
                clean[k] = float(v)
            except (TypeError, ValueError):
                continue
        row = {
            "ts": time.time(),
            "host": self.host,
            "step": int(step),
            "bucket": bucket,
            "event": event,
            "metrics": clean,
        }
        line = json.dumps(row)
        with self._lock:
            self._f.write(line + "\n")
            self._f.flush()
        # Gauges ride heartbeats + dryrun artifacts via the registry.
        from repro.obs.registry import get_registry

        reg = get_registry()
        for k, v in clean.items():
            reg.set_gauge(f"health/{bucket}/{k}", v)

    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None


_MONITOR = HealthMonitor(os.environ.get("REPRO_HEALTH") or None)


def get_monitor() -> HealthMonitor:
    """THE process-wide health monitor (disabled unless configured)."""
    return _MONITOR


def configure(path: Optional[str], host: Optional[str] = None,
              sample_every: Optional[int] = None) -> HealthMonitor:
    """(Re)configure the process monitor — what a worker does at boot from
    ``ElasticConfig.health_path``. ``path=None`` disables. Idempotent on
    the same path (keeps appending). ``REPRO_HEALTH`` is the env
    override, mirroring ``REPRO_TRACE``."""
    global _MONITOR
    if (
        _MONITOR.path == path
        and (host is None or _MONITOR.host == host)
        and (sample_every is None or _MONITOR.sample_every == sample_every)
    ):
        return _MONITOR
    old = _MONITOR
    _MONITOR = HealthMonitor(
        path, host=host,
        sample_every=(sample_every if sample_every is not None
                      else old.sample_every),
    )
    old.close()
    return _MONITOR


def read_health(path: str) -> List[Dict[str, Any]]:
    """All well-formed rows of a health.jsonl (torn trailing lines from a
    killed writer are skipped, like ``read_trace``)."""
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    row = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(row, dict) and "bucket" in row and "step" in row:
                    out.append(row)
    except FileNotFoundError:
        pass
    return out


# ---------------------------------------------------------------------------
# Device-side refresh emitters (called INSIDE the optimizer's jitted update)
# ---------------------------------------------------------------------------
def emit_refresh_matrix(label: str, g_loader, p_old, p_new, refreshed,
                        count):
    """Refresh-boundary metrics for a stacked matrix bucket, from inside
    the jitted update. ``g_loader()`` gives the (B,m,n) canonical gradient
    the refresh just consumed, ``p_old``/``p_new`` (B,n,r), ``refreshed``
    the (B,) bool mask. Everything, the load of G included, runs under
    ``lax.cond(any(refreshed))`` so non-refresh steps execute nothing —
    zero extra G traffic — and ships
    through ``jax.debug.callback`` (no value flows back: numerics are
    untouched). No-op (checked at trace time) when the monitor is
    disabled, so untraced runs compile bit-identical programs."""
    mon = get_monitor()
    if not mon.enabled:
        return
    import jax
    import jax.numpy as jnp
    from jax import lax

    def send(count_, n_ref, energy, resid, overlap):
        mon.record(int(count_), label, "refresh", {
            "n_refreshed": n_ref,
            "energy": energy,
            "eqn6_residual": resid,
            "subspace_overlap": overlap,
        })

    def do():
        g32 = g_loader().astype(jnp.float32)
        pn = p_new.astype(jnp.float32)
        po = p_old.astype(jnp.float32)
        mask = refreshed.astype(jnp.float32)

        # Per-stacked-element reduction: everything but axis 0 (the
        # bucket axis the ``refreshed`` mask indexes). Leaves may carry
        # extra leading dims beyond (B, m, n) — e.g. layer-stacked
        # (B, L, m, n) buckets — so reductions are ellipsis-shaped.
        def bsum(x):
            return jnp.sum(x.reshape(x.shape[0], -1), axis=1)

        gp = jnp.einsum("...mn,...nr->...mr", g32, pn)
        g_sq = bsum(g32 * g32)
        gp_sq = bsum(gp * gp)
        energy = gp_sq / jnp.maximum(g_sq, 1e-30)
        # ‖G − G P Pᵀ‖² = ‖G‖² − 2‖GP‖² + tr((GP)ᵀ(GP)·PᵀP): the r×r
        # trace identity — never materializes the m×n reconstruction.
        ptp = jnp.einsum("...nr,...ns->...rs", pn, pn)
        quad = bsum(jnp.einsum("...mr,...ms,...rs->...", gp, gp, ptp))
        resid = jnp.maximum(
            1.0 - 2.0 * energy + quad / jnp.maximum(g_sq, 1e-30), 0.0
        )
        # Column-normalized overlap: Eqn-6 P is not orthonormal, so the
        # raw ‖P_oldᵀP_new‖²/r would conflate scale with drift.
        pon = po / jnp.maximum(
            jnp.linalg.norm(po, axis=-2, keepdims=True), 1e-30
        )
        pnn = pn / jnp.maximum(
            jnp.linalg.norm(pn, axis=-2, keepdims=True), 1e-30
        )
        ov = jnp.einsum("...nr,...ns->...rs", pon, pnn)
        n_mats = max(
            1, int(jnp.size(pn) // (pn.shape[0] * pn.shape[-2] * pn.shape[-1]))
        )
        overlap = bsum(ov * ov) / (pn.shape[-1] * n_mats)
        n_ref = jnp.sum(mask)
        denom = jnp.maximum(n_ref, 1.0)

        def masked_mean(x):
            return jnp.sum(x * mask) / denom

        jax.debug.callback(
            send, count, n_ref, masked_mean(energy), masked_mean(resid),
            masked_mean(overlap),
        )

    lax.cond(jnp.any(refreshed), do, lambda: None)


def emit_refresh_conv(label: str, g32, po_old, pi_old, p_o, p_i,
                      refreshed, count):
    """Refresh-boundary metrics for a stacked Tucker-2 conv bucket:
    captured core energy (via column-normalized factors, so it is a true
    fraction) and the per-mode factor overlap, averaged. Same
    cond + debug.callback structure as the matrix emitter."""
    mon = get_monitor()
    if not mon.enabled:
        return
    import jax
    import jax.numpy as jnp
    from jax import lax
    from repro.core.conv import project_core

    def send(count_, n_ref, energy, overlap):
        mon.record(int(count_), label, "refresh", {
            "n_refreshed": n_ref,
            "energy": energy,
            "subspace_overlap": overlap,
        })

    def _colnorm(p):
        return p / jnp.maximum(
            jnp.linalg.norm(p.astype(jnp.float32), axis=1, keepdims=True),
            1e-30,
        )

    def do():
        mask = refreshed.astype(jnp.float32)
        pon, pin = _colnorm(p_o), _colnorm(p_i)
        core = project_core(g32.astype(jnp.float32), pon, pin)
        axes = tuple(range(1, g32.ndim))
        g_sq = jnp.sum(jnp.square(g32.astype(jnp.float32)), axis=axes)
        c_sq = jnp.sum(jnp.square(core), axis=tuple(range(1, core.ndim)))
        energy = c_sq / jnp.maximum(g_sq, 1e-30)

        def mode_overlap(old, new):
            ov = jnp.einsum(
                "bnr,bns->brs", _colnorm(old), _colnorm(new)
            )
            return jnp.sum(ov * ov, axis=(1, 2)) / new.shape[-1]

        overlap = 0.5 * (
            mode_overlap(po_old, p_o) + mode_overlap(pi_old, p_i)
        )
        n_ref = jnp.sum(mask)
        denom = jnp.maximum(n_ref, 1.0)
        jax.debug.callback(
            send, count, n_ref,
            jnp.sum(energy * mask) / denom,
            jnp.sum(overlap * mask) / denom,
        )

    lax.cond(jnp.any(refreshed), do, lambda: None)


# ---------------------------------------------------------------------------
# Host-side sampled observation (state only — structurally zero G reads)
# ---------------------------------------------------------------------------
def _find_projected_states(node, out: list) -> None:
    """Collect every optimizer-state node carrying (count, leaves) —
    ProjectedAdamState / ProjectedAdafactorState inside a possibly nested
    chain tuple — without importing the jax-heavy core modules."""
    if hasattr(node, "leaves") and hasattr(node, "count"):
        out.append(node)
        return
    if isinstance(node, (tuple, list)):
        for child in node:
            _find_projected_states(child, out)


def observe_state(opt_state, step: int,
                  monitor: Optional[HealthMonitor] = None) -> int:
    """Sampled host-side health pass over an optimizer state: per-bucket
    int8 codec stats (rail/saturation rate, non-finite scale fraction,
    relative quant error) and the ``sync_codes`` EF-sidecar rms. Reads
    ONLY the optimizer state — never the gradient — so the hot step path
    keeps exactly zero extra G round-trips. Stacked-state layouts only
    (the deployment default); per-leaf states are skipped silently.
    Returns the number of rows recorded."""
    mon = monitor or get_monitor()
    if not mon.enabled:
        return 0
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    states: list = []
    _find_projected_states(opt_state, states)
    n_rows = 0
    for st in states:
        leaves = st.leaves
        layout = getattr(leaves, "layout", None)
        buckets = getattr(leaves, "buckets", None)
        if layout is None or buckets is None:
            continue
        for info, leaf in zip(layout.buckets, buckets):
            label = bucket_label(info.kind, info.shape, info.dtype)
            mets: Dict[str, Any] = {}
            for name in ("m", "v"):
                q = getattr(leaf, name, None)
                scale = getattr(leaf, name + "_scale", None)
                if q is None or scale is None:
                    continue
                if jnp.dtype(q.dtype) != jnp.int8:
                    continue
                stats = kops.rowblock_code_stats(q, scale)
                for k, v in stats.items():
                    mets[f"{name}_{k}"] = v
            ef = getattr(leaf, "ef", None)
            if ef is not None:
                ef32 = ef.astype(jnp.float32)
                mets["ef_rms"] = jnp.sqrt(jnp.mean(jnp.square(ef32)))
            if not mets:
                continue
            # ONE transfer for the bucket's whole stat dict.
            fetched = jax.device_get(mets)
            mon.record(step, label, "sample",
                       {k: float(v) for k, v in fetched.items()})
            n_rows += 1
    return n_rows


# ---------------------------------------------------------------------------
# Analysis: journal rows -> typed verdicts
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class HealthReport:
    """The ``coap-health/v1`` artifact: per-bucket metric summaries and
    typed verdicts. Unknown verdict strings from a NEWER writer round-trip
    untouched (forward compat): consumers render them as-is and the
    solver ignores verdicts it does not recognize."""

    buckets: Dict[str, Dict[str, Any]]
    verdicts: List[str]
    thresholds: Dict[str, float]
    codec: str = HEALTH_CODEC_V1

    def ok(self) -> bool:
        return not self.verdicts

    def to_dict(self) -> Dict[str, Any]:
        return {
            "codec": self.codec,
            "buckets": self.buckets,
            "verdicts": list(self.verdicts),
            "thresholds": dict(self.thresholds),
        }

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "HealthReport":
        codec = d.get("codec", "")
        if not str(codec).startswith("coap-health/"):
            raise ValueError(
                f"not a coap-health artifact (codec {codec!r})"
            )
        return cls(
            buckets=dict(d.get("buckets") or {}),
            verdicts=list(d.get("verdicts") or []),
            thresholds=dict(d.get("thresholds") or {}),
            codec=str(codec),
        )

    def save(self, path: str) -> None:
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, indent=1, sort_keys=True)
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "HealthReport":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def _finite(values) -> List[float]:
    return [float(v) for v in values
            if isinstance(v, (int, float)) and math.isfinite(v)]


def analyze(rows: List[Dict[str, Any]],
            thresholds: Optional[Dict[str, float]] = None) -> HealthReport:
    """Pure pass: journal rows -> :class:`HealthReport`. Safe on empty,
    partial and unknown-schema rows (skips anything malformed) — exactly
    what ``fleet_status`` runs on an operator box."""
    th = dict(DEFAULT_THRESHOLDS)
    th.update(thresholds or {})
    by_bucket: Dict[str, List[Dict[str, Any]]] = {}
    for r in rows:
        if not isinstance(r, dict):
            continue
        label = r.get("bucket")
        if not isinstance(label, str) or not isinstance(
            r.get("metrics"), dict
        ):
            continue
        by_bucket.setdefault(label, []).append(r)

    buckets: Dict[str, Dict[str, Any]] = {}
    for label in sorted(by_bucket):
        rs = sorted(
            by_bucket[label],
            key=lambda r: (r.get("step", 0), r.get("ts", 0.0)),
        )
        refresh = [r["metrics"] for r in rs if r.get("event") == "refresh"]
        samples = [r["metrics"] for r in rs if r.get("event") == "sample"]
        warm = refresh[int(th["warmup_refreshes"]):]
        metrics: Dict[str, float] = {}
        verdicts: List[str] = []

        energies = _finite(m.get("energy") for m in (warm or refresh))
        if energies:
            med = statistics.median(energies)
            metrics["energy_median"] = med
            if med < th["energy_floor"]:
                verdicts.append(VERDICT_RANK_STARVED)

        overlaps = _finite(m.get("subspace_overlap") for m in warm)
        if len(overlaps) >= 2:
            ov = statistics.median(overlaps)
            metrics["overlap_median"] = ov
            if ov < th["overlap_floor"]:
                verdicts.append(VERDICT_SUBSPACE_THRASH)

        resids = _finite(m.get("eqn6_residual") for m in refresh)
        if resids:
            metrics["eqn6_residual_last"] = resids[-1]

        sat_rates, nonfinite, err_rels = [], [], []
        for m in samples:
            for k, v in m.items():
                if not isinstance(v, (int, float)) or not math.isfinite(v):
                    if k.endswith("scale_nonfinite"):
                        nonfinite.append(1.0)
                    continue
                if k.endswith("sat_rate"):
                    sat_rates.append(float(v))
                elif k.endswith("scale_nonfinite"):
                    nonfinite.append(float(v))
                elif k.endswith("err_rel"):
                    err_rels.append(float(v))
        if sat_rates:
            metrics["sat_rate_max"] = max(sat_rates)
        if nonfinite:
            metrics["scale_nonfinite_max"] = max(nonfinite)
        if err_rels:
            metrics["quant_err_rel_median"] = statistics.median(err_rels)
        if (nonfinite and max(nonfinite) > 0.0) or (
            sat_rates and max(sat_rates) > th["sat_rate_max"]
        ):
            verdicts.append(VERDICT_QUANT_SATURATED)

        efs = _finite(m.get("ef_rms") for m in samples
                      if "ef_rms" in m)
        if len(efs) >= int(th["ef_min_samples"]):
            k = max(1, len(efs) // 3)
            first = sum(efs[:k]) / k
            last = sum(efs[-k:]) / k
            ratio = last / first if first > 0 else (
                math.inf if last > 0 else 1.0
            )
            metrics["ef_growth_ratio"] = (
                ratio if math.isfinite(ratio) else 1e30
            )
            if ratio > th["ef_growth_max"]:
                verdicts.append(VERDICT_EF_NOT_DRAINING)

        buckets[label] = {
            "verdicts": verdicts,
            "metrics": metrics,
            "n_refresh": len(refresh),
            "n_sample": len(samples),
        }

    all_verdicts = sorted(
        {v for b in buckets.values() for v in b["verdicts"]}
    )
    return HealthReport(buckets=buckets, verdicts=all_verdicts,
                        thresholds=th)


def analyze_journal(path: str,
                    thresholds: Optional[Dict[str, float]] = None,
                    tail: int = 0) -> HealthReport:
    """:func:`analyze` over a journal file (``tail`` > 0 limits to the
    newest rows — what ``fleet_status`` uses for a live view)."""
    rows = read_health(path)
    if tail > 0:
        rows = rows[-tail:]
    return analyze(rows, thresholds=thresholds)
