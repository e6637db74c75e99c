"""Structured logging + metrics registry (counters/gauges/histograms).

The reference's only observability is print statements with [INFO]/[ERROR]
prefixes (SURVEY.md §5.1/§5.5). Here: stdlib logging with a single
namespaced logger tree (opt-in JSON lines carrying the active trace ID via
``CASSMANTLE_LOG_FORMAT=json``), plus an in-process metrics registry
surfaced by the server's /metrics route — JSON snapshot by default,
Prometheus text exposition under ``Accept: text/plain``.

Timings are **fixed-bucket cumulative histograms**, not sample lists: the
old keep-last-1024 trim silently turned p50/p99 into sliding-window stats
(and indexed p99 off-by-one at small n); buckets make memory constant per
series, percentiles all-time, and the exposition Prometheus-native
(``_bucket{le=...}/_sum/_count``). The JSON snapshot keeps the historical
``count/mean_s/p50_s/p99_s`` shape, with percentiles now interpolated
from the cumulative bucket counts.

Metric names are dotted lowercase (``subsystem.metric``), with dynamic
segments (queue/breaker names) interpolated in the middle; timing
histograms end ``_s`` (seconds) and size histograms ``_size``.
``tools/check_metrics.py`` lints every literal emission site against this
convention and the catalog in ``docs/OBSERVABILITY.md``.
"""

from __future__ import annotations

import bisect
import json
import logging
import os
import threading
import time
from collections import OrderedDict
from contextlib import contextmanager
from typing import Dict, List, Optional, Sequence, Tuple

# Latency-shaped default bounds: sub-ms host work through cold-compile
# minutes. Overridable per-process via ObsConfig.latency_buckets_s
# (set_default_buckets) and per-series via observe(buckets=...).
DEFAULT_BUCKETS_S: Tuple[float, ...] = (
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
)

_LOGGER_LOCK = threading.Lock()


class JsonLogFormatter(logging.Formatter):
    """One JSON object per line, carrying the active trace ID so a
    request's log lines and its `/debugz` trace join on one key."""

    def format(self, record: logging.LogRecord) -> str:
        payload = {
            "ts": self.formatTime(record),
            "level": record.levelname,
            "logger": record.name,
            "msg": record.getMessage(),
        }
        try:
            # lazy: utils.logging must stay importable before (and
            # without) the obs package — never a module-level cycle
            from cassmantle_tpu.obs.trace import current_trace_id

            trace_id = current_trace_id()
        except Exception:
            trace_id = None
        if trace_id:
            payload["trace_id"] = trace_id
        if record.exc_info:
            payload["exc"] = self.formatException(record.exc_info)
        return json.dumps(payload, ensure_ascii=False)


def _make_formatter() -> logging.Formatter:
    if os.environ.get("CASSMANTLE_LOG_FORMAT", "").lower() == "json":
        return JsonLogFormatter()
    return logging.Formatter(
        "%(asctime)s %(levelname)s %(name)s %(message)s"
    )


def get_logger(name: str) -> logging.Logger:
    logger = logging.getLogger(f"cassmantle.{name}")
    root = logging.getLogger("cassmantle")
    if not root.handlers:
        # double-checked under a lock: two threads racing the bare
        # check above would each attach a handler and duplicate every
        # log line for the life of the process
        with _LOGGER_LOCK:
            if not root.handlers:
                handler = logging.StreamHandler()
                handler.setFormatter(_make_formatter())
                root.addHandler(handler)
                root.setLevel(logging.INFO)
                root.propagate = False
    return logger


LabelsKey = Tuple[Tuple[str, str], ...]
SeriesKey = Tuple[str, LabelsKey]


def _series_key(name: str, labels: Optional[Dict[str, str]]) -> SeriesKey:
    if not labels:
        return name, ()
    return name, tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _flat_name(key: SeriesKey) -> str:
    """JSON-snapshot key: plain name, or name{k="v",...} when labeled —
    unlabeled series (every pre-existing name) keep their exact keys."""
    name, labels = key
    if not labels:
        return name
    inner = ",".join(f'{k}="{v}"' for k, v in labels)
    return f"{name}{{{inner}}}"


class Histogram:
    """Cumulative fixed-bucket histogram: constant memory per series,
    all-time percentile estimates via in-bucket linear interpolation.

    ``exemplars`` maps a bucket index to the LAST retained trace that
    landed in that bucket — ``(trace_id, value, unix_ts)`` — so a p99
    spike in any dashboard dereferences in one hop to a full waterfall
    at ``/debugz?trace=``. Bounded by construction (one slot per
    bucket); only rendered by the OpenMetrics exposition and the
    ``?exemplars=1`` JSON form, never by :meth:`Metrics.prometheus`."""

    __slots__ = ("bounds", "counts", "total", "sum", "exemplars")

    def __init__(self, bounds: Sequence[float]) -> None:
        self.bounds = tuple(sorted(float(b) for b in bounds))
        assert self.bounds, "histogram needs at least one bucket bound"
        self.counts = [0] * (len(self.bounds) + 1)  # last = +Inf overflow
        self.total = 0
        self.sum = 0.0
        self.exemplars: Dict[int, Tuple[str, float, float]] = {}

    def observe(self, value: float) -> None:
        # Prometheus buckets are le= (inclusive upper bounds)
        self.counts[bisect.bisect_left(self.bounds, value)] += 1
        self.total += 1
        self.sum += float(value)

    def quantile(self, q: float) -> float:
        """Estimate the q-quantile (0..1). Values in the +Inf overflow
        bucket report the top finite bound — a lower bound on the true
        quantile (size your buckets to cover the tail you care about)."""
        if self.total == 0:
            return 0.0
        rank = q * self.total
        cum = 0
        for i, count in enumerate(self.counts):
            if count and cum + count >= rank:
                if i >= len(self.bounds):      # overflow bucket
                    return self.bounds[-1]
                lo = self.bounds[i - 1] if i > 0 else 0.0
                hi = self.bounds[i]
                return lo + (hi - lo) * ((rank - cum) / count)
            cum += count
        return self.bounds[-1]

    def mean(self) -> float:
        return self.sum / self.total if self.total else 0.0


def _prom_name(name: str, labels: LabelsKey) -> Tuple[str, str]:
    """(metric_name, label_suffix) in Prometheus grammar: dots/dashes to
    underscores, ``cassmantle_`` namespace prefix, the ``_s`` seconds
    suffix expanded to ``_seconds`` per convention."""
    base = name.replace(".", "_").replace("-", "_")
    if base.endswith("_s"):
        base = base[:-2] + "_seconds"
    suffix = ""
    if labels:
        inner = ",".join(
            '{}="{}"'.format(k, v.replace("\\", "\\\\").replace('"', '\\"'))
            for k, v in labels)
        suffix = "{" + inner + "}"
    return "cassmantle_" + base, suffix


class Metrics:
    """Thread-safe counters/gauges/histograms. One global registry per
    process; instantiable standalone (golden tests use fresh instances)."""

    def __init__(self,
                 default_buckets: Sequence[float] = DEFAULT_BUCKETS_S
                 ) -> None:
        self._lock = threading.Lock()
        self._counters: Dict[SeriesKey, float] = {}
        self._gauges: Dict[SeriesKey, float] = {}
        self._hists: Dict[SeriesKey, Histogram] = {}
        self._default_buckets = tuple(default_buckets)
        # exemplar machinery (ISSUE 18): an injected source answers
        # "which trace is this observation from, and is that trace
        # already durably retained?" — (trace_id, certain). Certain
        # observations write their bucket exemplar immediately;
        # uncertain ones (a pending tail-sampled trace whose retention
        # verdict lands at root completion) park as candidates until
        # retain_exemplars/discard_exemplars resolves them. A fresh
        # Metrics() has no source, so exemplars are strictly opt-in.
        self._exemplar_source = None
        self._exemplar_pending: \
            "OrderedDict[str, List[Tuple[Histogram, int, float, float]]]" \
            = OrderedDict()
        self._exemplar_pending_cap = 256

    def set_default_buckets(self, bounds: Sequence[float]) -> None:
        """Default bounds for histograms created AFTER this call;
        existing series keep their buckets (cumulative counts cannot be
        re-binned)."""
        with self._lock:
            self._default_buckets = tuple(bounds)

    def inc(self, name: str, value: float = 1.0,
            labels: Optional[Dict[str, str]] = None) -> None:
        key = _series_key(name, labels)
        with self._lock:
            self._counters[key] = self._counters.get(key, 0.0) + value

    def gauge(self, name: str, value: float,
              labels: Optional[Dict[str, str]] = None) -> None:
        with self._lock:
            self._gauges[_series_key(name, labels)] = value

    def remove_gauge(self, name: str,
                     labels: Optional[Dict[str, str]] = None) -> None:
        """Retract a gauge series. Gauges are point-in-time readings:
        when their source disappears (a device whose memory_stats went
        dark mid-flight, obs/device.py) the honest export is ABSENCE —
        a frozen last value would be read as current truth by every
        later scrape. No-op when the series never existed."""
        with self._lock:
            self._gauges.pop(_series_key(name, labels), None)

    def observe(self, name: str, value: float,
                labels: Optional[Dict[str, str]] = None,
                buckets: Optional[Sequence[float]] = None) -> None:
        """Record into the series' histogram. ``buckets`` applies only
        on first observation of a series (fixing its bounds for life)."""
        key = _series_key(name, labels)
        source = self._exemplar_source
        tagged = source() if source is not None else None
        with self._lock:
            hist = self._hists.get(key)
            if hist is None:
                hist = Histogram(buckets or self._default_buckets)
                self._hists[key] = hist
            hist.observe(value)
            if tagged is not None:
                trace_id, certain = tagged
                idx = bisect.bisect_left(hist.bounds, value)
                if certain:
                    hist.exemplars[idx] = (trace_id, float(value),
                                           time.time())
                else:
                    slots = self._exemplar_pending.get(trace_id)
                    if slots is None:
                        slots = []
                        self._exemplar_pending[trace_id] = slots
                        while len(self._exemplar_pending) > \
                                self._exemplar_pending_cap:
                            self._exemplar_pending.popitem(last=False)
                    slots.append((hist, idx, float(value), time.time()))

    def observe_nowait(self, name: str, values: Sequence[float]) -> bool:
        """Record ``values`` into the series' histogram, all of them or,
        when the registry's lock is taken, none: False, at once. For
        code that can run while its own thread holds that lock, where
        :meth:`observe` would wait for ever: a ``gc.callbacks`` entry
        runs wherever a collection starts, the sections under this lock
        included. No exemplar."""
        key = _series_key(name, None)
        if not self._lock.acquire(blocking=False):
            return False
        try:
            hist = self._hists.get(key)
            if hist is None:
                hist = Histogram(self._default_buckets)
                self._hists[key] = hist
            for value in values:
                hist.observe(value)
        finally:
            self._lock.release()
        return True

    # -- exemplars (ISSUE 18) ---------------------------------------------
    def set_exemplar_source(self, fn) -> None:
        """Install the trace-association callback ``fn() -> None |
        (trace_id, certain)`` called on every histogram observation.
        The obs layer owns the policy (ambient span context, kill
        switch); this registry only stores the linkage."""
        self._exemplar_source = fn

    def retain_exemplars(self, trace_id: str) -> None:
        """A pending trace was tail-retained: promote its parked
        candidate observations into their buckets' exemplar slots
        (last-writer-wins = last retained trace per bucket)."""
        with self._lock:
            for hist, idx, value, ts in \
                    self._exemplar_pending.pop(trace_id, ()):
                hist.exemplars[idx] = (trace_id, value, ts)

    def discard_exemplars(self, trace_id: str) -> None:
        """A pending trace was dropped at root completion: its parked
        candidates must never surface as exemplars."""
        with self._lock:
            self._exemplar_pending.pop(trace_id, None)

    @contextmanager
    def timer(self, name: str, labels: Optional[Dict[str, str]] = None):
        start = time.perf_counter()
        try:
            yield
        finally:
            self.observe(name, time.perf_counter() - start, labels=labels)

    # -- registry reads (SLO engine, obs/slo.py) ---------------------------
    def counter_total(self, name: str) -> float:
        """Sum of a counter across ALL its label sets (per-room labels
        must aggregate to worker truth for SLO ratios)."""
        with self._lock:
            return sum(v for (n, _), v in self._counters.items()
                       if n == name)

    def gauge_values(self, name: str) -> List[float]:
        """Every label set's current value for a gauge (callers pick
        max/min as the conservative aggregate)."""
        with self._lock:
            return [v for (n, _), v in self._gauges.items() if n == name]

    def hist_totals(self, name: str
                    ) -> Optional[Tuple[Tuple[float, ...],
                                        Tuple[int, ...], int]]:
        """(bounds, bucket counts, total) for a histogram, summed across
        label sets sharing the first-seen bounds (one process = one
        bucket ladder per name by construction); None when the series
        has never been observed."""
        with self._lock:
            bounds = None
            counts: List[int] = []
            total = 0
            for (n, _), h in self._hists.items():
                if n != name:
                    continue
                if bounds is None:
                    bounds = h.bounds
                    counts = list(h.counts)
                    total = h.total
                elif h.bounds == bounds:
                    counts = [a + b for a, b in zip(counts, h.counts)]
                    total += h.total
            if bounds is None:
                return None
            return bounds, tuple(counts), total

    # -- federation (cluster /metrics, server/app.py) ----------------------
    def dump_state(self) -> Dict[str, list]:
        """Full-fidelity JSON-serializable registry state — what a peer
        ships for cluster federation. Unlike :meth:`snapshot`, histogram
        BUCKETS survive, so a merge is exact, not re-estimated."""
        with self._lock:
            return {
                "counters": [[k[0], [list(p) for p in k[1]], v]
                             for k, v in self._counters.items()],
                "gauges": [[k[0], [list(p) for p in k[1]], v]
                           for k, v in self._gauges.items()],
                "hists": [[k[0], [list(p) for p in k[1]],
                           list(h.bounds), list(h.counts), h.sum, h.total]
                          for k, h in self._hists.items()],
            }

    def merge_hist_state(self, name: str, labels: Optional[Dict[str, str]],
                         bounds: Sequence[float], counts: Sequence[int],
                         total_sum: float, total: int) -> bool:
        """Fold one shipped histogram into this registry. Same bounds →
        bucket counts add elementwise (the EXACT merge — every worker
        runs the same fixed ladders by construction); returns False on a
        bounds mismatch so the caller can fall back to a per-worker
        labeled series instead of silently mis-binning."""
        bounds = tuple(float(b) for b in bounds)
        key = _series_key(name, labels)
        with self._lock:
            hist = self._hists.get(key)
            if hist is None:
                hist = Histogram(bounds)
                self._hists[key] = hist
            if hist.bounds != bounds:
                return False
            hist.counts = [a + int(b)
                           for a, b in zip(hist.counts, counts)]
            hist.total += int(total)
            hist.sum += float(total_sum)
            return True

    # -- exposition -------------------------------------------------------
    def snapshot(self, exemplars: bool = False) -> Dict[str, object]:
        """The backward-compatible JSON shape: flat counters/gauges plus
        ``timings`` entries of ``{count, mean_s, p50_s, p99_s}`` (the
        ``_s`` keys are historical; non-seconds histograms like
        ``*.batch_size`` report their native unit under them).
        ``exemplars=True`` (the ``/metrics?exemplars=1`` form) adds a
        top-level ``exemplars`` map — per histogram, per bucket upper
        bound, the last retained trace — WITHOUT touching the default
        key set (pinned backward-compatible)."""
        with self._lock:
            timings = {
                _flat_name(key): {
                    "count": h.total,
                    "mean_s": h.mean(),
                    "p50_s": h.quantile(0.5),
                    "p99_s": h.quantile(0.99),
                }
                for key, h in self._hists.items() if h.total
            }
            out: Dict[str, object] = {
                "counters": {_flat_name(k): v
                             for k, v in self._counters.items()},
                "gauges": {_flat_name(k): v
                           for k, v in self._gauges.items()},
                "timings": timings,
            }
            if exemplars:
                ex: Dict[str, dict] = {}
                for key, h in self._hists.items():
                    if not h.exemplars:
                        continue
                    per = {}
                    for idx, (tid, value, ts) in \
                            sorted(h.exemplars.items()):
                        le = ("+Inf" if idx >= len(h.bounds)
                              else repr(float(h.bounds[idx])))
                        per[le] = {"trace_id": tid, "value": value,
                                   "ts": ts}
                    ex[_flat_name(key)] = per
                out["exemplars"] = ex
            return out

    def prometheus(self) -> str:
        """Text exposition (format version 0.0.4): counters as
        ``*_total``, gauges plain, histograms as cumulative
        ``_bucket{le=...}`` + ``_sum`` + ``_count``. Deterministically
        sorted so scrapes (and golden tests) are stable."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = {k: (h.bounds, tuple(h.counts), h.sum, h.total)
                     for k, h in self._hists.items()}
        lines = []
        typed = set()

        def _emit_type(pname: str, kind: str) -> None:
            if pname not in typed:
                typed.add(pname)
                lines.append(f"# TYPE {pname} {kind}")

        def _fmt(v: float) -> str:
            return repr(v) if isinstance(v, float) and not v.is_integer() \
                else str(int(v))

        for key in sorted(counters):
            pname, suffix = _prom_name(key[0], key[1])
            _emit_type(pname + "_total", "counter")
            lines.append(f"{pname}_total{suffix} {_fmt(counters[key])}")
        for key in sorted(gauges):
            pname, suffix = _prom_name(key[0], key[1])
            _emit_type(pname, "gauge")
            lines.append(f"{pname}{suffix} {_fmt(gauges[key])}")
        for key in sorted(hists):
            bounds, counts, total_sum, total = hists[key]
            pname, suffix = _prom_name(key[0], key[1])
            _emit_type(pname, "histogram")
            label_body = suffix[1:-1] + "," if suffix else ""
            cum = 0
            for bound, count in zip(bounds, counts):
                cum += count
                lines.append(
                    f'{pname}_bucket{{{label_body}le="{_fmt(bound)}"}} '
                    f"{cum}")
            cum += counts[-1]
            lines.append(f'{pname}_bucket{{{label_body}le="+Inf"}} {cum}')
            lines.append(f"{pname}_sum{suffix} {repr(float(total_sum))}")
            lines.append(f"{pname}_count{suffix} {total}")
        return "\n".join(lines) + "\n"

    def openmetrics(self) -> str:
        """OpenMetrics 1.0 text exposition (the
        ``application/openmetrics-text`` negotiation): same series as
        :meth:`prometheus` — counters declared on their BASE name with
        ``_total`` samples per the OpenMetrics grammar — plus
        ``# {trace_id="..."} value ts`` exemplar annotations on
        histogram ``_bucket`` lines and the mandatory ``# EOF``
        terminator. The plain Prometheus exposition stays byte-identical
        (exemplars render ONLY here and in ``snapshot(exemplars=True)``)."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = {k: (h.bounds, tuple(h.counts), h.sum, h.total,
                         dict(h.exemplars))
                     for k, h in self._hists.items()}
        lines = []
        typed = set()

        def _emit_type(pname: str, kind: str) -> None:
            if pname not in typed:
                typed.add(pname)
                lines.append(f"# TYPE {pname} {kind}")

        def _fmt(v: float) -> str:
            return repr(v) if isinstance(v, float) and not v.is_integer() \
                else str(int(v))

        def _exemplar(ex) -> str:
            if ex is None:
                return ""
            trace_id, value, ts = ex
            return (f' # {{trace_id="{trace_id}"}} '
                    f"{repr(float(value))} {repr(float(ts))}")

        for key in sorted(counters):
            pname, suffix = _prom_name(key[0], key[1])
            _emit_type(pname, "counter")
            lines.append(f"{pname}_total{suffix} {_fmt(counters[key])}")
        for key in sorted(gauges):
            pname, suffix = _prom_name(key[0], key[1])
            _emit_type(pname, "gauge")
            lines.append(f"{pname}{suffix} {_fmt(gauges[key])}")
        for key in sorted(hists):
            bounds, counts, total_sum, total, exemplars = hists[key]
            pname, suffix = _prom_name(key[0], key[1])
            _emit_type(pname, "histogram")
            label_body = suffix[1:-1] + "," if suffix else ""
            cum = 0
            for i, (bound, count) in enumerate(zip(bounds, counts)):
                cum += count
                lines.append(
                    f'{pname}_bucket{{{label_body}le="{_fmt(bound)}"}} '
                    f"{cum}{_exemplar(exemplars.get(i))}")
            cum += counts[-1]
            lines.append(
                f'{pname}_bucket{{{label_body}le="+Inf"}} {cum}'
                f"{_exemplar(exemplars.get(len(bounds)))}")
            lines.append(f"{pname}_sum{suffix} {repr(float(total_sum))}")
            lines.append(f"{pname}_count{suffix} {total}")
        lines.append("# EOF")
        return "\n".join(lines) + "\n"


def _parse_labels(raw) -> Optional[Dict[str, str]]:
    if not raw:
        return None
    return {str(k): str(v) for k, v in raw}


def merge_states(states: Sequence[Tuple[str, Dict[str, list]]]
                 ) -> "Metrics":
    """Fold per-worker :meth:`Metrics.dump_state` payloads into one
    registry — the cluster view (`/metrics?scope=cluster`):

    - **counters sum** exactly (they are deltas of the same events);
    - **gauges get a ``worker`` label** — a point-in-time value per
      process has no meaningful sum, but the per-worker spread is
      exactly what an operator reads (which worker's loop is lagging);
    - **histograms merge exactly**: every worker runs the same fixed
      bucket ladders by construction, so bucket counts add elementwise;
      a bounds mismatch (a mid-rollout config skew) falls back to a
      per-worker labeled series rather than mis-binning.
    """
    merged = Metrics()
    for worker, state in states:
        for name, labels, value in state.get("counters", []):
            merged.inc(name, value, labels=_parse_labels(labels))
        for name, labels, value in state.get("gauges", []):
            lbl = dict(_parse_labels(labels) or {})
            lbl["worker"] = worker
            merged.gauge(name, value, labels=lbl)
        for name, labels, bounds, counts, hsum, total in \
                state.get("hists", []):
            if not merged.merge_hist_state(name, _parse_labels(labels),
                                           bounds, counts, hsum, total):
                lbl = dict(_parse_labels(labels) or {})
                lbl["worker"] = worker
                merged.merge_hist_state(name, lbl, bounds, counts,
                                        hsum, total)
    return merged


class _NullMetrics:
    """A no-op registry with the Metrics emission surface. The canary
    probe Game (obs/prober.py) runs the REAL engine code paths but must
    leave zero marks on player-facing series (``game.guesses`` feeds
    leaderboard dashboards; cache counters feed capacity planning), so
    it swaps this in for its instance-level emissions. Reads are not
    supported on purpose — nothing should aggregate from a null sink."""

    def inc(self, name, value=1.0, labels=None):
        pass

    def gauge(self, name, value, labels=None):
        pass

    def remove_gauge(self, name, labels=None):
        pass

    def observe(self, name, value, labels=None, buckets=None):
        pass

    @contextmanager
    def timer(self, name, labels=None):
        yield


NULL_METRICS = _NullMetrics()

metrics = Metrics()
