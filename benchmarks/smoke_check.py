"""CI gate over benchmark JSON emissions (the ``BENCH_*.json`` trajectory).

A benchmark that runs but emits NaN timings or zero GFLOP/s rows is worse
than one that crashes — it seeds the perf history with garbage that later
regression checks would diff against. This checker fails the job instead:

  python -m benchmarks.smoke_check BENCH_*.json

Rules, per record ({"section", "name", "us_per_call", "derived"}):
  * ``us_per_call`` must be finite and >= 0 (exactly 0 is allowed only for
    analytic rows such as the break-even table, which report no timing);
  * every ``gflops=<v>`` field in ``derived`` must be finite and > 0;
  * a file with zero records fails (an empty emission means the benchmark
    silently did nothing).

Cross-row rule (the chunked-psum overlap gate): for every
``.../sellcs+merge@Pdev/chunks=<c>/k=<k>`` group emitted by
``benchmarks.spmm_sweep --chunks``, IF the sweep's own roofline
prediction (the ``model_us`` derived field) says some pipelined depth
should be at least as fast as the monolithic fixup, then the BEST
measured chunked row (c > 1) must not run more than
``CHUNK_REGRESSION_TOLERANCE`` slower than the ``chunks=1`` row — where
the model says overlap pays, pipelining must never cost real time, only
hide it. Groups where the model itself predicts chunking loses (tiny
smoke matrices, launch-dominated psums, host-platform meshes with no
async collectives) are recorded but not gated — failing them would
punish the code for physics the model already prices.

Second cross-row rule (the 2-D mesh gate): for every
``.../sellcs+<sched>@PdxPmmesh[/chunks=<c>]/k=<k>`` group emitted by
``benchmarks.spmm_sweep --mesh``, rows that factor the same device total
are compared across mesh shapes: IF the traffic model (``model_us``)
says some model-sharded shape (``Pm > 1``) is at least as fast as the
pure-data (``Pm = 1``) shape, the best measured model-sharded row must
not run more than ``MESH_REGRESSION_TOLERANCE`` slower than the pure-data
row — where the model says the model axis pays, column-sharding X/Y must
never cost real time. Groups where the model predicts the model axis
loses (small k, stream-dominated) are recorded but not gated, and so are
rows measured on a backend without per-device memory (``backend=cpu`` —
a host-platform mesh keeps "replicated" X as one shared buffer, so the
model-axis byte saving is physically unobservable there and a measured
loss is mesh overhead, not a bug).

Third cross-row rule (the compact-gather gate): for every
``.../sellcs+<sched>@<mesh>[/chunks=<c>]/cx=<on|off>/k=<k>`` pair emitted
by ``benchmarks.spmm_sweep --compact-x on,off``, IF the traffic model
(``model_us``, priced with the partitioner's measured mean ``n_touched``)
says the sparsity-aware X gather is STRICTLY faster than replication, the
measured ``cx=on`` row must not run more than
``COMPACT_REGRESSION_TOLERANCE`` slower than its ``cx=off`` twin — where
the model says the gather pays, compaction must never cost real time.
Rows where the model predicts the gather does not strictly win — losses
AND the exact tie of near-dense columns (``n_touched`` capped at ``n``
makes the modelled figures equal while the gather's unpriced overhead
remains) — are recorded but not gated, matching the selector's
tie-refusal; and — like the mesh gate — so are ``backend=cpu`` rows: a
host-platform mesh keeps X as one shared buffer, so the gather's byte
saving is physically unobservable there.

Fifth cross-row rule (the gather-overlap gate): for every compacted row
group differing only in the ``gx=<mode>`` segment emitted by
``benchmarks.spmm_sweep --gather`` (the up-front baseline keeps its
unsuffixed name), IF the exposed-gather roofline term (the
``exposed_gather_us`` derived field) says some hidden-gather schedule
(``overlap``'s per-span double-buffer) strictly shrinks the exposed gather time, the best measured hidden row
must not run more than ``GATHER_REGRESSION_TOLERANCE`` slower than the
up-front row — where the model says hiding the gather pays, hiding it
must never cost real time. Groups where the model prices the schedules
equally (row schedule, single chunk — overlap degenerates to up-front)
are recorded but not gated, and — like the mesh/compact gates — so are
``backend=cpu`` rows: a host-platform mesh shares one X buffer, so the
hidden bytes cannot show up in wall time.

Fourth cross-row rule (the transpose gate): for every
``.../op=N|T/k=<k>`` pair emitted by ``benchmarks.spmm_sweep --op N,T``,
the measured ``op=T`` row must stay within
``TRANSPOSE_REGRESSION_TOLERANCE`` of the op-aware traffic model's
predicted N-to-T slowdown applied to its ``op=N`` twin — the
scatter-accumulate transpose may cost what the extra priced traffic
costs, never more. ``backend=cpu`` rows are recorded but not gated (the
host-platform mesh shares one buffer, so the priced deltas cannot show
up in wall time).

Residual rule (the model-honesty gate): every ``residual=<v>`` derived
field (``benchmarks.spmm_sweep``) and every record in an ``repro.obs/v1``
document's ``"residuals"`` list (``launch.serve --metrics``) must be
finite and > 0 — a NaN/zero residual means one side of the
observed-vs-modeled pairing was garbage. On a backend with per-device
memory the gate additionally flags residuals outside
``[1/RESIDUAL_MAX_OFF, RESIDUAL_MAX_OFF]`` (model off by more than 10x
where it claims to apply); ``backend=cpu`` rows only get the finiteness
check — the traffic model prices HBM and ICI a host-platform mesh does
not have, so a huge cpu residual is expected, not a bug.

A ``repro.obs/v1`` document (a dict, not a record list — the schema
``launch.serve --metrics`` dumps) is validated structurally too: every
histogram's count/sum finite, quantiles ordered (p50 <= p95 <= p99), and
counters non-negative.

Migration rule (the online break-even gate): a document whose base labels
carry ``migrate=auto|force`` (``launch.serve --migrate``) must show the
controller actually ran — ``serve/multiplies_total`` present and at least
the stamped ``requests`` label (every served column counted), and the
``serve/breakeven_estimate`` gauge present. ``force`` mode additionally
requires the swap to have landed (``serve/plan_swaps`` >= 1, a positive
``serve/swap_unix_s``, finite positive ``serve/convert_s``) and a finite
positive break-even estimate (both of its sides were measured by then).
``auto`` mode gates neither the swap nor finiteness: below-break-even
traffic honestly never converts and an infinite estimate just means no
saving was found. The pre/post-migration flush latency comparison
(post-swap p50 must not regress past the pre-swap p99) is armed only off
``backend=cpu`` and only when both phase histograms are non-empty — a
forced swap can land after the last flush, and a host-platform mesh's
latencies do not reflect the byte model the migration optimizes.

Fleet rule (the serve-SLO gate): a document whose base labels carry
``mode=fleet`` (``launch.serve --mode fleet``) must show every tenant
actually served — a non-empty per-tenant ``fleet/flush_s`` histogram and
a per-tenant ``batcher/served`` counter of at least the stamped
``requests`` label (the flush stream never drops a queued request). When
the ``fail_device`` label is set, the device loss must have been handled
mid-stream: ``fleet/device_losses`` >= 1, at least one ``fleet/redeal_s``
re-deal latency observation, and at least one tenant with post-loss
flushes (``fleet/flush_postloss_s``). The SLO-attainment latency check
(per-tenant p50 flush within the ``slo_ms`` budget) is armed only off
``backend=cpu`` — host-platform flush latencies are compile- and
dispatch-dominated, not the byte economics the SLO budget prices.

``spmvs_to_amortize=inf`` and friends are legitimate (a format that never
breaks even), so only the keys named above are validated.
"""
from __future__ import annotations

import json
import math
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

# derived keys that must be finite and strictly positive
_POSITIVE_KEYS = ("gflops",)
# row-name prefixes whose us_per_call is analytic (no timing collected)
_ANALYTIC_PREFIXES = ("break_even.",)

# best chunked merge row may be at most 10% slower than the monolithic one
CHUNK_REGRESSION_TOLERANCE = 1.10

# best model-sharded (Pm > 1) mesh row may be at most 10% slower than the
# pure-data (Pm = 1) row of the same device total, where the model says the
# model axis pays
MESH_REGRESSION_TOLERANCE = 1.10

# a cx=on (sparsity-aware X gather) row may be at most 10% slower than its
# cx=off twin, where the model says the gather pays
COMPACT_REGRESSION_TOLERANCE = 1.10

# the best hidden-gather (gx=overlap) row may be at most 10% slower
# than its up-front twin, where the exposed-gather model says hiding pays
GATHER_REGRESSION_TOLERANCE = 1.10

# observed/modeled residuals outside [1/10, 10] flag the model as broken —
# on backends where the model claims to apply (never on cpu, where the
# traffic model prices memory systems the host platform does not have)
RESIDUAL_MAX_OFF = 10.0

# an op=T row may be at most this factor slower than the op-aware model's
# predicted N-to-T slowdown applied to its op=N twin (scatter fixups are
# noisier than the streaming forward rows, so the slack is wider than the
# 10% same-shape gates)
TRANSPOSE_REGRESSION_TOLERANCE = 1.25

_CHUNK_ROW_RE = re.compile(
    r"^(?P<base>.*sellcs\+merge@\d+dev)/chunks=(?P<c>\d+)"
    r"(?P<cx>/cx=(?:on|off))?(?P<gx>/gx=(?:upfront|overlap))?"
    r"(?P<op>/op=[NT])?/k=(?P<k>\d+)$")

_MESH_ROW_RE = re.compile(
    r"^(?P<base>.*sellcs\+(?:row|merge))@(?P<pd>\d+)x(?P<pm>\d+)mesh"
    r"(?P<chunks>/chunks=\d+)?(?P<cx>/cx=(?:on|off))?"
    r"(?P<gx>/gx=(?:upfront|overlap))?"
    r"(?P<op>/op=[NT])?/k=(?P<k>\d+)$")

_COMPACT_ROW_RE = re.compile(
    r"^(?P<base>.*sellcs\+(?:row|merge)@(?:\d+dev|\d+x\d+mesh)"
    r"(?:/chunks=\d+)?)/cx=(?P<cx>on|off)"
    r"(?P<gx>/gx=(?:upfront|overlap))?"
    r"(?P<op>/op=[NT])?/k=(?P<k>\d+)$")

_TRANSPOSE_ROW_RE = re.compile(
    r"^(?P<base>.*sellcs\+(?:row|merge)@(?:\d+dev|\d+x\d+mesh)"
    r"(?:/chunks=\d+)?(?:/cx=(?:on|off))?"
    r"(?:/gx=(?:upfront|overlap))?)/op=(?P<op>[NT])/k=(?P<k>\d+)$")

_GATHER_ROW_RE = re.compile(
    r"^(?P<base>.*sellcs\+(?:row|merge)@(?:\d+dev|\d+x\d+mesh)"
    r"(?:/chunks=\d+)?/cx=on)(?P<gx>/gx=(?:upfront|overlap))?"
    r"(?P<op>/op=[NT])?/k=(?P<k>\d+)$")


def _derived_fields(derived: str) -> Iterator[Tuple[str, str]]:
    for part in derived.split(";"):
        if "=" in part:
            key, val = part.split("=", 1)
            yield key.strip(), val.strip()


def _derived_float(rec: dict, want: str) -> Optional[float]:
    for key, val in _derived_fields(str(rec.get("derived", ""))):
        if key == want:
            try:
                v = float(val)
            except ValueError:
                return None
            return v if math.isfinite(v) else None
    return None


def _model_us(rec: dict) -> Optional[float]:
    return _derived_float(rec, "model_us")


def _exposed_gather_us(rec: dict) -> Optional[float]:
    return _derived_float(rec, "exposed_gather_us")


def _backend(rec: dict) -> Optional[str]:
    for key, val in _derived_fields(str(rec.get("derived", ""))):
        if key == "backend":
            return val
    # harness.Csv stamps the backend as a top-level record key; the
    # derived field (older spmm_sweep rows) stays authoritative when both
    # are present since it names the backend the row actually timed
    b = rec.get("backend")
    return str(b) if b is not None else None


def _check_residual_value(v: float, backend: Optional[str], where: str
                          ) -> List[str]:
    """Shared residual validation: finite and > 0 everywhere; the 10x
    model-off flag only where the model claims to apply (not cpu)."""
    if not math.isfinite(v) or v <= 0:
        return [f"{where}: residual={v} must be finite and > 0"]
    if backend not in (None, "cpu") and \
            not (1.0 / RESIDUAL_MAX_OFF <= v <= RESIDUAL_MAX_OFF):
        return [f"{where}: residual={v:.4g} — model off by more than "
                f"{RESIDUAL_MAX_OFF:g}x on backend={backend} where it "
                "claims to apply"]
    return []


def check_residuals(records: List[dict], origin: str) -> List[str]:
    """The model-honesty gate over ``residual=`` derived fields."""
    problems = []
    for rec in records:
        name = f"{origin}:{rec.get('section', '?')}/{rec.get('name', '?')}"
        for key, val in _derived_fields(str(rec.get("derived", ""))):
            if key != "residual":
                continue
            try:
                v = float(val)
            except ValueError:
                problems.append(f"{name}: residual={val!r} is not a "
                                "number")
                continue
            problems.extend(
                _check_residual_value(v, _backend(rec), name))
    return problems


def check_obs_document(doc: dict, origin: str) -> List[str]:
    """Validate one ``repro.obs/v1`` document (``launch.serve --metrics``
    / ``MetricRegistry.dump``): structural sanity for every series plus
    the residual gate over the ledger's records."""
    problems = []
    base_backend = doc.get("labels", {}).get("backend")
    for c in doc.get("counters", []):
        v = c.get("value")
        if not isinstance(v, (int, float)) or not math.isfinite(v) or v < 0:
            problems.append(f"{origin}:counter/{c.get('name', '?')}: "
                            f"value={v!r} must be finite and >= 0")
    for h in doc.get("histograms", []):
        name = f"{origin}:histogram/{h.get('name', '?')}"
        count = h.get("count")
        if not isinstance(count, int) or count < 0:
            problems.append(f"{name}: count={count!r} must be an int >= 0")
            continue
        if count == 0:
            continue
        for key in ("sum", "min", "max", "mean", "p50", "p95", "p99"):
            v = h.get(key)
            if not isinstance(v, (int, float)) or not math.isfinite(v):
                problems.append(f"{name}: {key}={v!r} is not finite")
        qs = [h.get(k) for k in ("p50", "p95", "p99")]
        if all(isinstance(q, (int, float)) and math.isfinite(q)
               for q in qs) and not (qs[0] <= qs[1] <= qs[2]):
            problems.append(f"{name}: quantiles out of order "
                            f"(p50={qs[0]!r}, p95={qs[1]!r}, "
                            f"p99={qs[2]!r})")
    for r in doc.get("residuals", []):
        name = f"{origin}:residual/{r.get('name', '?')}"
        backend = r.get("labels", {}).get("backend", base_backend)
        v = r.get("residual")
        if not isinstance(v, (int, float)):
            problems.append(f"{name}: residual={v!r} is not a number")
            continue
        problems.extend(_check_residual_value(float(v), backend, name))
    problems.extend(check_migration(doc, origin))
    problems.extend(check_slo(doc, origin))
    return problems


def check_slo(doc: dict, origin: str) -> List[str]:
    """The serve-SLO gate over a ``launch.serve --mode fleet`` run's
    document. Armed only when the base labels carry ``mode=fleet`` (any
    other document passes untouched)."""
    labels = doc.get("labels", {})
    if labels.get("mode") != "fleet":
        return []
    problems = []
    try:
        tenants = int(labels.get("tenants", ""))
    except (TypeError, ValueError):
        return [f"{origin}: mode=fleet but the tenants label is missing "
                "or not an int"]
    try:
        requests = float(labels.get("requests", "nan"))
    except (TypeError, ValueError):
        requests = math.nan

    def by_tenant(coll):
        # per-series lookup on (name, tenant label); fleet-wide series
        # carry no tenant key and land under (name, None)
        return {(s.get("name"), s.get("labels", {}).get("tenant")): s
                for s in doc.get(coll, [])}

    counters = by_tenant("counters")
    hists = by_tenant("histograms")
    try:
        slo_s = float(labels.get("slo_ms", "nan")) / 1e3
    except (TypeError, ValueError):
        slo_s = math.nan
    gate_latency = labels.get("backend") not in (None, "cpu")
    for i in range(tenants):
        t = f"t{i}"
        h = hists.get(("fleet/flush_s", t))
        if not (h and h.get("count")):
            problems.append(f"{origin}: tenant {t}: fleet/flush_s "
                            "histogram missing or empty — the tenant "
                            "never served a flush")
            continue
        served = counters.get(("batcher/served", t), {}).get("value")
        if not isinstance(served, (int, float)) or \
                not math.isfinite(served):
            problems.append(f"{origin}: tenant {t}: batcher/served "
                            "counter missing — served requests went "
                            "uncounted")
        elif math.isfinite(requests) and served < requests:
            problems.append(f"{origin}: tenant {t}: served={served:g} < "
                            f"requests={requests:g} — the flush stream "
                            "dropped queued requests")
        p50 = h.get("p50")
        if gate_latency and math.isfinite(slo_s) and \
                isinstance(p50, (int, float)) and math.isfinite(p50) and \
                p50 > slo_s:
            problems.append(f"{origin}: tenant {t}: p50 flush latency "
                            f"{p50:.4g}s exceeds the slo_ms budget "
                            f"({slo_s:.4g}s) on backend="
                            f"{labels.get('backend')}")
    fail = labels.get("fail_device", "")
    if fail not in ("", "none", "None", None):
        losses = counters.get(("fleet/device_losses", None),
                              {}).get("value")
        if not (isinstance(losses, (int, float)) and losses >= 1):
            problems.append(f"{origin}: fail_device={fail} but "
                            f"fleet/device_losses={losses!r} — the "
                            "injected loss was never handled")
        redeals = sum(int(h.get("count") or 0)
                      for (name, _), h in hists.items()
                      if name == "fleet/redeal_s")
        if redeals < 1:
            problems.append(f"{origin}: fail_device={fail} but no "
                            "fleet/redeal_s observation — no plan was "
                            "re-dealt across the survivors")
        post = any(h.get("count") for (name, _), h in hists.items()
                   if name == "fleet/flush_postloss_s")
        if not post:
            problems.append(f"{origin}: fail_device={fail} but every "
                            "fleet/flush_postloss_s histogram is empty — "
                            "nothing was served after the loss")
    return problems


def check_migration(doc: dict, origin: str) -> List[str]:
    """The online break-even gate over a ``launch.serve --migrate`` run's
    document. Armed only when the base labels carry ``migrate=auto`` or
    ``migrate=force`` (any other document passes untouched)."""
    labels = doc.get("labels", {})
    mode = labels.get("migrate")
    if mode not in ("auto", "force"):
        return []
    problems = []
    counters = {c.get("name"): c.get("value")
                for c in doc.get("counters", [])}
    gauges = {g.get("name"): g.get("value") for g in doc.get("gauges", [])}
    hists = {h.get("name"): h for h in doc.get("histograms", [])}

    def num(v):
        return v if isinstance(v, (int, float)) else math.nan

    mult = num(counters.get("serve/multiplies_total", math.nan))
    try:
        requests = float(labels.get("requests", "nan"))
    except (TypeError, ValueError):
        requests = math.nan
    if not math.isfinite(mult):
        problems.append(f"{origin}: migrate={mode} but "
                        "serve/multiplies_total is missing — the "
                        "controller never counted the traffic")
    elif math.isfinite(requests) and mult < requests:
        problems.append(f"{origin}: serve/multiplies_total={mult:g} < "
                        f"requests={requests:g} — served columns went "
                        "uncounted")
    be = gauges.get("serve/breakeven_estimate")
    if be is None:
        problems.append(f"{origin}: migrate={mode} but "
                        "serve/breakeven_estimate gauge is missing")
    swaps = num(counters.get("serve/plan_swaps", 0.0))
    if mode == "force":
        # a forced run must have landed the swap and measured both sides
        # of the break-even; auto mode may honestly never convert
        if not (swaps >= 1):
            problems.append(f"{origin}: migrate=force but "
                            f"serve/plan_swaps={swaps:g} — the forced "
                            "migration never landed")
        if not (num(gauges.get("serve/swap_unix_s", math.nan)) > 0):
            problems.append(f"{origin}: migrate=force but "
                            "serve/swap_unix_s is missing or not > 0")
        conv = num(gauges.get("serve/convert_s", math.nan))
        if not (math.isfinite(conv) and conv > 0):
            problems.append(f"{origin}: migrate=force but "
                            f"serve/convert_s={conv!r} is not a finite "
                            "positive measured build time")
        if be is not None and not (math.isfinite(num(be)) and num(be) > 0):
            problems.append(f"{origin}: migrate=force but "
                            f"serve/breakeven_estimate={be!r} is not "
                            "finite and > 0 after a measured conversion")
    # latency sanity across the swap: only where per-device memory makes
    # the comparison physical, and only when the swap landed mid-traffic
    # (a force swap can land after the last flush -> empty post hist)
    pre = hists.get("serve/flush_premigrate_s")
    post = hists.get("serve/flush_postmigrate_s")
    if labels.get("backend") not in (None, "cpu") and swaps >= 1 and \
            pre and post and pre.get("count") and post.get("count"):
        p99_pre, p50_post = num(pre.get("p99")), num(post.get("p50"))
        if math.isfinite(p99_pre) and math.isfinite(p50_post) and \
                p50_post > p99_pre:
            problems.append(
                f"{origin}: post-migration p50 flush latency "
                f"({p50_post:.4g}s) exceeds the pre-migration p99 "
                f"({p99_pre:.4g}s) — the conversion the controller chose "
                "made serving slower")
    return problems


def check_chunk_regressions(records: List[dict], origin: str) -> List[str]:
    """The overlap gate: per (merge-row base, k) group whose own roofline
    prediction says some pipelined depth beats the monolithic fixup, the
    fastest measured chunked row must stay within
    CHUNK_REGRESSION_TOLERANCE of the chunks=1 row."""
    groups: Dict[Tuple[str, str, str],
                 Dict[int, Tuple[float, Optional[float]]]] = {}
    for rec in records:
        m = _CHUNK_ROW_RE.match(str(rec.get("name", "")))
        us = rec.get("us_per_call")
        if not m or not isinstance(us, (int, float)) or not \
                math.isfinite(us) or us <= 0:
            continue
        # a cx=on row only compares against chunked cx=on rows (and off
        # against off, gx against the same gx, op=T against op=T) —
        # compaction changes the X bytes under the stream, the gather
        # schedule moves them, and the transpose changes the fixup
        # direction
        groups.setdefault((m["base"], m["cx"] or "", m["gx"] or "",
                           m["op"] or "", m["k"]),
                          {})[int(m["c"])] = (float(us), _model_us(rec))
    problems = []
    for (base, cx, gxseg, opseg, k), rows in sorted(groups.items()):
        mono = rows.get(1)
        chunked = {c: r for c, r in rows.items() if c > 1}
        if mono is None or not chunked:
            continue                    # nothing to compare against
        # arm the gate only where the model predicts overlap pays at THIS
        # size (otherwise a measured loss is the physics, not a bug)
        models = [r[1] for r in chunked.values()]
        if mono[1] is None or any(mu is None for mu in models) or \
                min(models) > mono[1]:
            continue
        best_c, (best_us, _) = min(chunked.items(), key=lambda t: t[1][0])
        if best_us > CHUNK_REGRESSION_TOLERANCE * mono[0]:
            problems.append(
                f"{origin}:{base}{cx}{gxseg}{opseg}/k={k}: "
                f"best chunked merge row "
                f"(chunks={best_c}, {best_us:.4g} us) regresses "
                f"{best_us / mono[0]:.2f}x over the monolithic chunks=1 "
                f"row ({mono[0]:.4g} us) although the model predicts "
                f"overlap pays here; tolerance is "
                f"{CHUNK_REGRESSION_TOLERANCE:.2f}x")
    return problems


def check_mesh_regressions(records: List[dict], origin: str) -> List[str]:
    """The 2-D mesh gate: per (row base, device total, chunks, k) group
    whose own traffic model says some model-sharded (Pm > 1) factorization
    is at least as fast as the pure-data (Pm = 1) one, the best measured
    model-sharded row must stay within MESH_REGRESSION_TOLERANCE of the
    pure-data row. Rows measured on a ``backend=cpu`` host-platform mesh
    are never gated — there the replicated X is one shared buffer, so the
    model-axis saving cannot show up in wall time."""
    groups: Dict[Tuple[str, int, str, str, str],
                 Dict[Tuple[int, int], Tuple[float, Optional[float]]]] = {}
    for rec in records:
        m = _MESH_ROW_RE.match(str(rec.get("name", "")))
        us = rec.get("us_per_call")
        if not m or not isinstance(us, (int, float)) or not \
                math.isfinite(us) or us <= 0:
            continue
        if _backend(rec) in (None, "cpu"):
            continue            # no per-device memory -> nothing to gate
        pd, pm = int(m["pd"]), int(m["pm"])
        key = (m["base"], pd * pm, m["chunks"] or "", m["cx"] or "",
               m["gx"] or "", m["op"] or "", m["k"])
        groups.setdefault(key, {})[(pd, pm)] = (float(us), _model_us(rec))
    problems = []
    for (base, total, chunks, cx, gxseg, opseg, k), rows in \
            sorted(groups.items()):
        pure = next((r for (pd, pm), r in rows.items() if pm == 1), None)
        sharded = {s: r for s, r in rows.items() if s[1] > 1}
        if pure is None or not sharded:
            continue                    # nothing to compare against
        # arm the gate only where the model predicts the model axis pays
        # at THIS size (otherwise a measured loss is physics, not a bug)
        models = [r[1] for r in sharded.values()]
        if pure[1] is None or any(mu is None for mu in models) or \
                min(models) > pure[1]:
            continue
        (bpd, bpm), (best_us, _) = min(sharded.items(),
                                       key=lambda t: t[1][0])
        if best_us > MESH_REGRESSION_TOLERANCE * pure[0]:
            problems.append(
                f"{origin}:{base}@{total}dev{chunks}{cx}{gxseg}{opseg}"
                f"/k={k}: best "
                f"model-sharded mesh row ({bpd}x{bpm}, {best_us:.4g} us) "
                f"regresses {best_us / pure[0]:.2f}x over the pure-data "
                f"row ({pure[0]:.4g} us) although the model predicts the "
                f"model axis pays here; tolerance is "
                f"{MESH_REGRESSION_TOLERANCE:.2f}x")
    return problems


def check_compact_regressions(records: List[dict], origin: str
                              ) -> List[str]:
    """The sparsity-aware-gather gate: per distributed row pair differing
    only in ``cx=on|off``, if the traffic model (priced with the measured
    mean ``n_touched``) says the compacted gather is STRICTLY faster than
    replication, the measured ``cx=on`` row must stay within
    COMPACT_REGRESSION_TOLERANCE of the ``cx=off`` row. A modelled tie
    never arms the gate (dense columns cap ``n_touched`` at ``n``, so the
    byte model sees a wash while the gather's overhead stays unpriced),
    and neither do ``backend=cpu`` rows — a host-platform mesh keeps X as
    one shared buffer, so the gather's byte saving cannot show up in wall
    time and a measured loss there is gather overhead on zero upside, not
    a bug."""
    groups: Dict[Tuple[str, str],
                 Dict[str, Tuple[float, Optional[float]]]] = {}
    for rec in records:
        m = _COMPACT_ROW_RE.match(str(rec.get("name", "")))
        us = rec.get("us_per_call")
        if not m or not isinstance(us, (int, float)) or not \
                math.isfinite(us) or us <= 0:
            continue
        if _backend(rec) in (None, "cpu"):
            continue            # shared X buffer -> nothing to gate
        # a gx=overlap row pairs with nothing here: the replicated
        # baseline has no gather to schedule, so only the up-front
        # (unsuffixed) cx=on row gets an off twin — hidden-gather rows
        # land in gx-keyed groups that never complete and are skipped
        groups.setdefault((m["base"], m["gx"] or "", m["op"] or "",
                           m["k"]),
                          {})[m["cx"]] = (float(us), _model_us(rec))
    problems = []
    for (base, gxseg, opseg, k), rows in sorted(groups.items()):
        off, on = rows.get("off"), rows.get("on")
        if off is None or on is None:
            continue                    # nothing to compare against
        # arm the gate only where the model predicts the gather STRICTLY
        # pays at THIS size: near-dense columns cap n_touched at n and
        # make the modelled figures exactly equal (the wash), and the
        # gather's own overhead is below the model's resolution — a
        # measured loss on the tie is physics, not a regression (the
        # selector refuses compaction on the same tie)
        if off[1] is None or on[1] is None or on[1] >= off[1]:
            continue
        if on[0] > COMPACT_REGRESSION_TOLERANCE * off[0]:
            problems.append(
                f"{origin}:{base}{gxseg}{opseg}/k={k}: "
                f"compacted-gather row (cx=on, "
                f"{on[0]:.4g} us) regresses {on[0] / off[0]:.2f}x over "
                f"the replicated-X row ({off[0]:.4g} us) although the "
                f"model predicts the gather pays here; tolerance is "
                f"{COMPACT_REGRESSION_TOLERANCE:.2f}x")
    return problems


def check_gather_overlap(records: List[dict], origin: str) -> List[str]:
    """The gather-overlap gate: per compacted row group differing only in
    the ``gx=<mode>`` segment (``benchmarks.spmm_sweep --gather``), if the
    exposed-gather roofline term (the ``exposed_gather_us`` derived field)
    says some hidden-gather schedule STRICTLY shrinks the exposed gather
    time, the best measured hidden row must stay within
    GATHER_REGRESSION_TOLERANCE of the up-front baseline — hiding the
    gather may only move bytes off the critical path, never add wall
    time. A modelled tie never arms the gate (the row schedule and the
    single-chunk merge degenerate overlap back to up-front, so the term
    is identical and a measured loss there is double-buffer overhead on
    zero upside), and neither do ``backend=cpu`` rows — a host-platform
    mesh shares one X buffer, so the hidden bytes cannot show up in wall
    time."""
    groups: Dict[Tuple[str, str, str],
                 Dict[str, Tuple[float, Optional[float]]]] = {}
    for rec in records:
        m = _GATHER_ROW_RE.match(str(rec.get("name", "")))
        us = rec.get("us_per_call")
        if not m or not isinstance(us, (int, float)) or not \
                math.isfinite(us) or us <= 0:
            continue
        if _backend(rec) in (None, "cpu"):
            continue            # shared X buffer -> nothing to gate
        mode = m["gx"][len("/gx="):] if m["gx"] else "upfront"
        groups.setdefault((m["base"], m["op"] or "", m["k"]),
                          {})[mode] = (float(us), _exposed_gather_us(rec))
    problems = []
    for (base, opseg, k), rows in sorted(groups.items()):
        up = rows.get("upfront")
        hidden = {g: r for g, r in rows.items() if g != "upfront"}
        if up is None or not hidden:
            continue                    # nothing to compare against
        # arm the gate only where the model predicts hiding STRICTLY
        # pays at THIS size (the degenerate schedules price identically
        # and a measured loss there is physics, not a regression)
        exposed = [r[1] for r in hidden.values()]
        if up[1] is None or any(e is None for e in exposed) or \
                min(exposed) >= up[1]:
            continue
        best_g, (best_us, _) = min(hidden.items(), key=lambda t: t[1][0])
        if best_us > GATHER_REGRESSION_TOLERANCE * up[0]:
            problems.append(
                f"{origin}:{base}{opseg}/k={k}: best hidden-gather row "
                f"(gx={best_g}, {best_us:.4g} us) regresses "
                f"{best_us / up[0]:.2f}x over the up-front gather row "
                f"({up[0]:.4g} us) although the model predicts hiding "
                f"pays here; tolerance is "
                f"{GATHER_REGRESSION_TOLERANCE:.2f}x")
    return problems


def check_transpose_regressions(records: List[dict], origin: str
                                ) -> List[str]:
    """The op-aware gate: per distributed row pair differing only in
    ``op=N|T`` (``benchmarks.spmm_sweep --op N,T``), the measured op=T
    row must stay within TRANSPOSE_REGRESSION_TOLERANCE of the op-aware
    model's predicted N-to-T slowdown applied to the measured op=N row —
    the scatter-accumulate transpose may cost what the extra traffic
    (dense slot-space X read, full-column partial, scatter psum) prices,
    but not more. ``backend=cpu`` rows are never gated: a host-platform
    mesh shares one buffer for everything, so the priced traffic deltas
    are physically unobservable there."""
    groups: Dict[Tuple[str, str],
                 Dict[str, Tuple[float, Optional[float]]]] = {}
    for rec in records:
        m = _TRANSPOSE_ROW_RE.match(str(rec.get("name", "")))
        us = rec.get("us_per_call")
        if not m or not isinstance(us, (int, float)) or not \
                math.isfinite(us) or us <= 0:
            continue
        if _backend(rec) in (None, "cpu"):
            continue            # no per-device memory -> nothing to gate
        groups.setdefault((m["base"], m["k"]), {})[m["op"]] = \
            (float(us), _model_us(rec))
    problems = []
    for (base, k), rows in sorted(groups.items()):
        fw, tr = rows.get("N"), rows.get("T")
        if fw is None or tr is None:
            continue                    # nothing to compare against
        if fw[1] is None or tr[1] is None or fw[1] <= 0:
            continue                    # no model prediction to arm on
        predicted = tr[1] / fw[1]       # the model's N-to-T slowdown
        allowed = TRANSPOSE_REGRESSION_TOLERANCE * predicted * fw[0]
        if tr[0] > allowed:
            problems.append(
                f"{origin}:{base}/k={k}: transpose row (op=T, "
                f"{tr[0]:.4g} us) runs {tr[0] / fw[0]:.2f}x the op=N row "
                f"({fw[0]:.4g} us) where the model predicts only "
                f"{predicted:.2f}x; tolerance is "
                f"{TRANSPOSE_REGRESSION_TOLERANCE:.2f}x the prediction")
    return problems


def check_records(records: List[dict], origin: str) -> List[str]:
    """Return a list of human-readable violations (empty == clean)."""
    problems = []
    if not records:
        problems.append(f"{origin}: no records — benchmark emitted nothing")
    for rec in records:
        name = f"{origin}:{rec.get('section', '?')}/{rec.get('name', '?')}"
        us = rec.get("us_per_call")
        if not isinstance(us, (int, float)) or not math.isfinite(us):
            problems.append(f"{name}: us_per_call={us!r} is not finite")
        elif us < 0:
            problems.append(f"{name}: us_per_call={us} is negative")
        elif us == 0 and not str(rec.get("name", "")).startswith(
                _ANALYTIC_PREFIXES):
            problems.append(f"{name}: us_per_call is 0 for a timed row")
        for key, val in _derived_fields(str(rec.get("derived", ""))):
            if key not in _POSITIVE_KEYS:
                continue
            try:
                v = float(val)
            except ValueError:
                problems.append(f"{name}: {key}={val!r} is not a number")
                continue
            if not math.isfinite(v) or v <= 0:
                problems.append(f"{name}: {key}={val} must be finite and "
                                "> 0")
    problems.extend(check_chunk_regressions(records, origin))
    problems.extend(check_mesh_regressions(records, origin))
    problems.extend(check_compact_regressions(records, origin))
    problems.extend(check_gather_overlap(records, origin))
    problems.extend(check_transpose_regressions(records, origin))
    problems.extend(check_residuals(records, origin))
    return problems


def main(argv=None) -> int:
    paths = list(argv if argv is not None else sys.argv[1:])
    if not paths:
        print("usage: python -m benchmarks.smoke_check BENCH_*.json",
              file=sys.stderr)
        return 2
    problems: List[str] = []
    total = 0
    for path in paths:
        try:
            with open(path) as f:
                records = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            problems.append(f"{path}: unreadable ({e})")
            continue
        if isinstance(records, dict) and \
                records.get("schema") == "repro.obs/v1":
            # a serve --metrics dump, not a harness record list
            total += (len(records.get("counters", []))
                      + len(records.get("gauges", []))
                      + len(records.get("histograms", []))
                      + len(records.get("residuals", [])))
            problems.extend(check_obs_document(records, path))
            continue
        total += len(records)
        problems.extend(check_records(records, path))
    if problems:
        print(f"smoke_check: {len(problems)} problem(s) in {len(paths)} "
              "file(s):", file=sys.stderr)
        for p in problems:
            print(f"  FAIL {p}", file=sys.stderr)
        return 1
    print(f"smoke_check: {total} records across {len(paths)} file(s) OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
