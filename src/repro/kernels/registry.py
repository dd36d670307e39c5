"""Declarative kernel registry — one `dispatch()` for every Pallas kernel,
plus the benchmark-driven dispatch policy that tunes its decisions.

Each kernel registers four things:

  pallas_fn   the Pallas entrypoint, called as pallas_fn(*args, interpret=…, **kw)
  ref_fn      the pure-jnp oracle from ref.py with the same call signature
              (minus `interpret`) and identical numerics contract
  eligible    a shape-eligibility predicate over the same arguments: False
              means the Pallas formulation cannot express this call (missing
              blocked structure, tile-misaligned shapes, d_qk != d_v, …)
  bucket      a shape-bucketing function over the same arguments: calls in the
              same bucket share one tuned dispatch decision (default: a single
              bucket per kernel)

`dispatch(name, *args, force_pallas=…, backend=…, **kw)` then picks exactly
one of three modes (`resolve_mode` exposes the decision for tests):

  "pallas"     compiled Pallas — eligible call on a TPU backend
  "interpret"  Pallas interpreter — eligible call, force_pallas=True off-TPU
               (the kernel-parity test path)
  "ref"        reference oracle — ineligible shapes, or off-TPU without
               force_pallas

A Pallas call that fails raises: no error is turned into an oracle run, so
a run that reports mode "pallas" ran the kernel. Routing to "ref" happens
only by decision (ineligible shape, off-TPU, policy, `mode_override`), and
`count_dispatches` makes every decision visible — `prune` reports the
(kernel, mode) pairs of each run in stats["kernel_dispatches"].

Dispatch policy
---------------

On top of the eligibility rules sits a measured-cost policy (`DispatchPolicy`):
a per-(kernel, backend, shape-bucket) table of tuned decisions, produced by
`tune()` (which times every candidate variant on the live backend) and
persisted to a JSON cache (`policy_path()`, overridable via the
``REPRO_DISPATCH_POLICY`` env var). `resolve_mode` consults the active policy
first; with no policy (or no entry for the bucket) it falls back to the
eligibility rules above, so an untuned checkout behaves exactly like
the pre-policy registry. `force_pallas` always bypasses the policy — parity
tests pin the kernel path.

The policy also stores *route* decisions for choices that live above a single
kernel call — today the `prune` routing (route names ``prune.lcc`` and
``prune.nlcc``, see core/lcc.py and core/nlcc.py): packed vs unpacked sweeps,
plus the fused multi-hop wave engine for NLCC (``ROUTE_FUSED``,
kernels/bitset_wave.py). `resolve_route` serves these to the hot loops.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import os
import time
import warnings
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import jax

MODE_PALLAS = "pallas"
MODE_INTERPRET = "interpret"
MODE_REF = "ref"
MODES = (MODE_PALLAS, MODE_INTERPRET, MODE_REF)

ROUTE_PACKED = "packed"
ROUTE_UNPACKED = "unpacked"
# the fused multi-hop NLCC wave (kernels/bitset_wave.py): one kernel call per
# wave instead of one bitset_spmm launch per hop
ROUTE_FUSED = "fused"
# the enumeration join (route name ``enumerate.join``, core/enumerate.py):
# host = the numpy row-table join over the compacted subgraph; device = the
# device-resident join over the execution-backend prims (core/join.py)
ROUTE_HOST = "host"
ROUTE_DEVICE = "device"
# row placement of the SHARDED device join (bucket ("sharded", mode)):
# replicated = every shard holds the full row table, slots psum-combined;
# rowsharded = rows live on their frontier-vertex owner shard and move via
# the keyed `exchange_rows` collective — per-shard memory ~1/P (the default)
ROUTE_REPLICATED = "replicated"
ROUTE_ROWSHARDED = "rowsharded"

# wildcard bucket: one decision for every shape of a (kernel, backend) pair
BUCKET_ANY = "*"


def _always_eligible(*args, **kwargs) -> bool:
    return True


def _single_bucket(*args, **kwargs) -> Tuple:
    return ()


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    name: str
    pallas_fn: Callable[..., Any]
    ref_fn: Callable[..., Any]
    eligible: Callable[..., bool]
    bucket: Callable[..., Tuple] = _single_bucket
    doc: str = ""


_REGISTRY: Dict[str, KernelSpec] = {}


def register(
    name: str,
    *,
    pallas: Callable[..., Any],
    ref: Callable[..., Any],
    eligible: Callable[..., bool] = _always_eligible,
    bucket: Callable[..., Tuple] = _single_bucket,
    doc: str = "",
) -> KernelSpec:
    """Register (or re-register) a kernel under `name`."""
    spec = KernelSpec(name=name, pallas_fn=pallas, ref_fn=ref,
                      eligible=eligible, bucket=bucket, doc=doc)
    _REGISTRY[name] = spec
    return spec


def get(name: str) -> KernelSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no kernel {name!r} registered; known: {sorted(_REGISTRY)}"
        ) from None


def names() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ------------------------------------------------------------------ buckets
def shape_bucket(*dims: int) -> Tuple[int, ...]:
    """Round each dimension up to the next power of two. Calls whose dims land
    in the same bucket share one tuned decision — the autotuner measures one
    representative per bucket, not every exact shape."""
    out = []
    for d in dims:
        d = max(int(d), 1)
        b = 1
        while b < d:
            b <<= 1
        out.append(b)
    return tuple(out)


def shard_bucket(P: int, *dims: int) -> Tuple:
    """Shard-aware shape bucket for decisions made inside the sharded
    execution backends (core/engine.py): keyed by the shard count AND the
    shard-LOCAL dimensions (power-of-two rounded), so a tuned choice for
    "p4 shards, 512 local vertices, wave 1024" never leaks onto a different
    mesh decomposition of the same global graph. Renders as e.g.
    ``p4x512x1024`` in policy-table keys."""
    return (f"p{int(P)}",) + shape_bucket(*dims)


def batch_bucket(B: int, bucket) -> Tuple:
    """Template-batched variant of an existing bucket: a leading ``b<B>``
    segment (power-of-two rounded batch size) so batched routes tune
    separately from single-query ones — renders as e.g. ``b8x2048x1024``.
    Lookups for batch size 1 (``b1x...``) fall back to the unbatched key
    (`DispatchPolicy._lookup`), so a pre-batching policy cache keeps
    resolving without re-tuning."""
    b = shape_bucket(B)[0]
    if bucket == BUCKET_ANY:
        return (f"b{b}",)
    return (f"b{b}",) + tuple(bucket)


def bucket_key(bucket) -> str:
    """Render a shape bucket the way policy-table keys spell it ("2048x32",
    "*", "scalar") — for reading measurements back out of a policy."""
    if bucket == BUCKET_ANY:
        return BUCKET_ANY
    return "x".join(str(b) for b in tuple(bucket)) or "scalar"


_bucket_key = bucket_key


def _entry_key(name: str, backend: str, bucket) -> str:
    return f"{name}|{backend}|{_bucket_key(bucket)}"


# ------------------------------------------------------------------- policy
@dataclasses.dataclass
class PolicyEntry:
    """One tuned decision: the winning variant plus the measurements behind
    it (candidate -> best wall seconds over the tuning repeats)."""

    choice: str
    measured_s: Dict[str, float] = dataclasses.field(default_factory=dict)

    def to_json(self) -> Dict:
        return {"choice": self.choice, "measured_s": self.measured_s}

    @staticmethod
    def from_json(d: Dict) -> "PolicyEntry":
        return PolicyEntry(
            choice=str(d["choice"]),
            measured_s={k: float(v) for k, v in d.get("measured_s", {}).items()},
        )


@dataclasses.dataclass
class PlanEntry:
    """One tuned *query plan* for a (template-signature, graph-stats) bucket:
    the ordered constraint phases — each a dict with the constraint signature
    (``"cycle:0,1,2,0"``), the engine choice (``"nlcc"``/``"tds"``), and the
    walk-direction choice (``"default"``/``"fwd"``/``"rev"``/``"head"``) —
    plus the cost model's prediction and any measured comparison."""

    phases: List[Dict] = dataclasses.field(default_factory=list)
    predicted_s: float = 0.0
    measured_s: Dict[str, float] = dataclasses.field(default_factory=dict)

    def signatures(self) -> List[str]:
        return [str(p["sig"]) for p in self.phases]

    def to_json(self) -> Dict:
        return {
            "phases": self.phases,
            "predicted_s": self.predicted_s,
            "measured_s": self.measured_s,
        }

    @staticmethod
    def from_json(d: Dict) -> "PlanEntry":
        phases = [dict(p) for p in d["phases"]]
        for p in phases:
            p["sig"]  # KeyError on malformed phase → entry skipped by caller
        return PlanEntry(
            phases=phases,
            predicted_s=float(d.get("predicted_s", 0.0)),
            measured_s={k: float(v) for k, v in d.get("measured_s", {}).items()},
        )


# The single plan-table route name: plan keys render as
# ``prune.plan|<backend>|<template-sig>x<stats-bucket>``.
PLAN_ROUTE = "prune.plan"

POLICY_SCHEMA_VERSION = 1


@dataclasses.dataclass
class DispatchPolicy:
    """Measured-cost dispatch table, keyed "<name>|<backend>|<bucket>".

    `modes` holds per-kernel mode decisions ("pallas"/"interpret"/"ref");
    `routes` holds above-kernel routing decisions ("packed"/"unpacked"/
    "fused"). Lookup tries the exact bucket first, then the ``*`` wildcard
    bucket.
    """

    modes: Dict[str, PolicyEntry] = dataclasses.field(default_factory=dict)
    routes: Dict[str, PolicyEntry] = dataclasses.field(default_factory=dict)
    plans: Dict[str, PlanEntry] = dataclasses.field(default_factory=dict)
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    # -- lookup
    def _lookup(self, table: Dict[str, PolicyEntry], name, backend, bucket):
        entry = table.get(_entry_key(name, backend, bucket))
        if (entry is None and isinstance(bucket, tuple)
                and bucket[:1] == ("b1",)):
            # batch-size-1 forward-compat: a pre-batching cache has no
            # ``b1`` entries, but its unbatched decision is exactly the
            # B=1 decision — resolve it before falling to the wildcard
            unbatched = bucket[1:] if len(bucket) > 1 else BUCKET_ANY
            entry = table.get(_entry_key(name, backend, unbatched))
        if entry is None and bucket != BUCKET_ANY:
            entry = table.get(_entry_key(name, backend, BUCKET_ANY))
        return entry

    def mode_for(self, name: str, backend: str, bucket) -> Optional[str]:
        entry = self._lookup(self.modes, name, backend, bucket)
        return entry.choice if entry is not None else None

    def route_for(self, name: str, backend: str, bucket) -> Optional[str]:
        entry = self._lookup(self.routes, name, backend, bucket)
        return entry.choice if entry is not None else None

    def route_entry_for(self, name: str, backend: str, bucket
                        ) -> Optional[PolicyEntry]:
        """Full tuned route entry (choice + measured_s), with the same
        exact-then-wildcard bucket lookup as `route_for` — the public way to
        read measurements back out (benchmarks, roll-ups)."""
        return self._lookup(self.routes, name, backend, bucket)

    def plan_for(self, backend: str, bucket) -> Optional["PlanEntry"]:
        """Tuned plan for a (template-sig, stats-bucket) bucket — exact key
        only: a plan never transfers across templates or graph-stats classes,
        so there is no wildcard fallback."""
        return self.plans.get(_entry_key(PLAN_ROUTE, backend, bucket))

    # -- mutation
    def set_mode(self, name: str, backend: str, bucket, choice: str,
                 measured_s: Optional[Dict[str, float]] = None):
        if choice not in MODES:
            raise ValueError(f"unknown mode {choice!r}; expected one of {MODES}")
        self.modes[_entry_key(name, backend, bucket)] = PolicyEntry(
            choice, dict(measured_s or {}))

    def set_route(self, name: str, backend: str, bucket, choice: str,
                  measured_s: Optional[Dict[str, float]] = None):
        self.routes[_entry_key(name, backend, bucket)] = PolicyEntry(
            choice, dict(measured_s or {}))

    def set_plan(self, backend: str, bucket, entry: "PlanEntry"):
        self.plans[_entry_key(PLAN_ROUTE, backend, bucket)] = entry

    # -- persistence
    def to_json(self) -> Dict:
        out = {
            "schema_version": POLICY_SCHEMA_VERSION,
            "meta": self.meta,
            "modes": {k: e.to_json() for k, e in sorted(self.modes.items())},
            "routes": {k: e.to_json() for k, e in sorted(self.routes.items())},
        }
        if self.plans:
            # additive field: a pre-plan reader's from_json ignores unknown
            # keys, so schema_version stays 1
            out["plans"] = {k: e.to_json() for k, e in sorted(self.plans.items())}
        return out

    @staticmethod
    def from_json(d: Dict) -> "DispatchPolicy":
        ver = d.get("schema_version")
        if ver != POLICY_SCHEMA_VERSION:
            raise ValueError(
                f"dispatch policy schema_version {ver!r} != "
                f"{POLICY_SCHEMA_VERSION}; re-run registry.tune()"
            )
        plans: Dict[str, PlanEntry] = {}
        for k, e in d.get("plans", {}).items():
            try:
                plans[k] = PlanEntry.from_json(e)
            except (KeyError, TypeError, ValueError) as err:
                # a malformed plan entry must not take down the mode/route
                # tables it rides along with — skip just the entry
                warnings.warn(
                    f"ignoring malformed plan cache entry {k!r}: {err}",
                    RuntimeWarning, stacklevel=2,
                )
        return DispatchPolicy(
            modes={k: PolicyEntry.from_json(e) for k, e in d.get("modes", {}).items()},
            routes={k: PolicyEntry.from_json(e) for k, e in d.get("routes", {}).items()},
            plans=plans,
            meta=dict(d.get("meta", {})),
        )

    def save(self, path: Optional[str] = None) -> str:
        path = path or policy_path()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=1, sort_keys=True)
        return path

    @staticmethod
    def load(path: Optional[str] = None) -> "DispatchPolicy":
        path = path or policy_path()
        with open(path) as f:
            return DispatchPolicy.from_json(json.load(f))


DEFAULT_POLICY_PATH = os.path.join("experiments", "policy", "dispatch_policy.json")


def policy_path() -> str:
    """Where the persisted policy cache lives (env REPRO_DISPATCH_POLICY wins)."""
    return os.environ.get("REPRO_DISPATCH_POLICY", DEFAULT_POLICY_PATH)


_POLICY_UNSET = object()
_POLICY: Any = _POLICY_UNSET


def set_policy(policy: Optional[DispatchPolicy]) -> None:
    """Install `policy` as the active dispatch policy (None = explicitly no
    policy: eligibility rules only, no lazy cache load)."""
    global _POLICY
    _POLICY = policy


def clear_policy() -> None:
    """Forget the active policy; the next lookup lazily re-reads the cache."""
    global _POLICY
    _POLICY = _POLICY_UNSET


def get_policy() -> Optional[DispatchPolicy]:
    """The active policy: whatever `set_policy` installed, else the persisted
    cache at `policy_path()` if one exists (loaded once), else None."""
    global _POLICY
    if _POLICY is _POLICY_UNSET:
        path = policy_path()
        if os.path.exists(path):
            try:
                _POLICY = DispatchPolicy.load(path)
            except (ValueError, KeyError, json.JSONDecodeError, OSError) as e:
                warnings.warn(
                    f"ignoring unreadable dispatch policy cache {path!r}: {e}",
                    RuntimeWarning, stacklevel=2,
                )
                _POLICY = None
        else:
            _POLICY = None
    return _POLICY


# ------------------------------------------------------ resilience seam
# `mode_override` is the degradation ladder's "ref rung" (core/resilience.py):
# every dispatch inside the context resolves to the given mode (in practice
# MODE_REF), sidestepping a kernel that keeps failing. force_pallas still
# wins — parity tests pin the kernel path even under an active ladder.
_MODE_OVERRIDE: Optional[str] = None

# `set_dispatch_hook` installs a callable invoked as hook(name, mode) right
# before every kernel executes; it may raise (the fault-injection seam). One
# hook at a time — dispatch is a global choke point.
_DISPATCH_HOOK: Optional[Callable[[str, str], None]] = None


@contextlib.contextmanager
def mode_override(mode: str):
    if mode not in MODES:
        raise ValueError(f"unknown mode {mode!r}; expected one of {MODES}")
    global _MODE_OVERRIDE
    prev = _MODE_OVERRIDE
    _MODE_OVERRIDE = mode
    try:
        yield
    finally:
        _MODE_OVERRIDE = prev


def set_dispatch_hook(hook: Optional[Callable[[str, str], None]]) -> None:
    global _DISPATCH_HOOK
    _DISPATCH_HOOK = hook


def get_dispatch_hook() -> Optional[Callable[[str, str], None]]:
    return _DISPATCH_HOOK


@contextlib.contextmanager
def dispatch_hook(hook: Callable[[str, str], None]):
    prev = _DISPATCH_HOOK
    set_dispatch_hook(hook)
    try:
        yield
    finally:
        set_dispatch_hook(prev)


@contextlib.contextmanager
def count_dispatches():
    """Count the (kernel, mode) pairs dispatched inside the context: yields a
    Counter keyed by (name, mode), filled as dispatches happen. A dispatch is
    counted once per call of `dispatch` — once per trace for kernels inside a
    jitted or looped program, once per call for eager ones. Any hook already
    installed still runs first; a dispatch it rejects is not counted."""
    counts: collections.Counter = collections.Counter()
    prev = _DISPATCH_HOOK

    def hook(name: str, mode: str) -> None:
        if prev is not None:
            prev(name, mode)
        counts[(name, mode)] += 1

    with dispatch_hook(hook):
        yield counts


def dispatch_report(counts) -> Dict[str, int]:
    """`count_dispatches` counts as {"kernel:mode": n}, sorted."""
    return {f"{name}:{mode}": int(n) for (name, mode), n in sorted(counts.items())}


def _modes_runnable(backend: str) -> Tuple[str, ...]:
    """Modes that can actually execute on `backend` (for an eligible call)."""
    if backend == "tpu":
        return (MODE_PALLAS, MODE_INTERPRET, MODE_REF)
    return (MODE_INTERPRET, MODE_REF)


# ----------------------------------------------------------------- routing
def resolve_mode(
    name: str,
    *args,
    force_pallas: bool = False,
    backend: Optional[str] = None,
    **kwargs,
) -> str:
    """The routing decision `dispatch` will take, without executing anything.

    Order: eligibility (a shape the kernel cannot express is always "ref"),
    then the tuned policy for this (kernel, backend, bucket) — skipped under
    force_pallas, which pins the kernel path for parity tests — then the
    untuned fallback (TPU -> pallas, forced -> interpret, else ref)."""
    spec = get(name)
    if not spec.eligible(*args, **kwargs):
        return MODE_REF
    be = backend or jax.default_backend()
    if _MODE_OVERRIDE is not None and not force_pallas:
        return _MODE_OVERRIDE
    if not force_pallas:
        policy = get_policy()
        if policy is not None:
            choice = policy.mode_for(name, be, spec.bucket(*args, **kwargs))
            if choice is not None and choice in _modes_runnable(be):
                return choice
    if be == "tpu":
        return MODE_PALLAS
    if force_pallas:
        return MODE_INTERPRET
    return MODE_REF


def resolve_route(
    name: str,
    bucket=BUCKET_ANY,
    *,
    default: str,
    backend: Optional[str] = None,
    allowed: Optional[Sequence[str]] = None,
) -> str:
    """Above-kernel routing decision (e.g. packed vs unpacked `prune` paths):
    the tuned policy's choice for (name, backend, bucket) when one exists,
    else `default` — which callers set to today's hardcoded behavior, so an
    untuned checkout routes exactly as before. With `allowed` set, a cache
    entry outside it (hand-edited typo, stale candidate name) falls back to
    `default` deterministically instead of leaking into comparisons."""
    be = backend or jax.default_backend()
    policy = get_policy()
    if policy is not None:
        choice = policy.route_for(name, be, bucket)
        if choice is not None and (allowed is None or choice in allowed):
            return choice
    return default


def resolve_plan(
    bucket,
    signatures: Sequence[str],
    *,
    backend: Optional[str] = None,
) -> Optional[PlanEntry]:
    """Tuned query plan for a (template-sig, stats-bucket) bucket, validated
    against the constraint signatures the template *currently* generates.

    Returns None (→ caller uses the paper's heuristic order) when there is no
    active policy, the policy has no plan for this bucket, or the cached plan
    is *stale*: its phase-signature multiset no longer matches `signatures`
    (the template changed, or constraint generation itself changed). Stale
    entries are ignored with a warning rather than half-applied — a plan that
    drops or invents a constraint is unsound, not just slow."""
    policy = get_policy()
    if policy is None or not policy.plans:
        return None
    be = backend or jax.default_backend()
    entry = policy.plan_for(be, bucket)
    if entry is None:
        return None
    if sorted(entry.signatures()) != sorted(str(s) for s in signatures):
        warnings.warn(
            f"ignoring stale plan cache entry for bucket "
            f"{_bucket_key(bucket)!r}: cached constraint signatures "
            f"{sorted(entry.signatures())} != current "
            f"{sorted(str(s) for s in signatures)}; re-run the planner",
            RuntimeWarning, stacklevel=2,
        )
        return None
    return entry


def dispatch(
    name: str,
    *args,
    force_pallas: bool = False,
    backend: Optional[str] = None,
    **kwargs,
):
    """Run kernel `name` through the mode `resolve_mode` picks."""
    spec = get(name)
    mode = resolve_mode(
        name, *args, force_pallas=force_pallas, backend=backend, **kwargs
    )
    if _DISPATCH_HOOK is not None:
        _DISPATCH_HOOK(name, mode)  # may raise: the fault-injection seam
    if mode == MODE_REF:
        return spec.ref_fn(*args, **kwargs)
    return spec.pallas_fn(*args, interpret=(mode == MODE_INTERPRET), **kwargs)


# ---------------------------------------------------------------- autotune
def _time_thunk(thunk: Callable[[], Any], repeat: int) -> float:
    """Best wall-time over `repeat` runs, after one warmup (compile) run;
    device work is synchronized out via block_until_ready."""

    def run_once():
        out = thunk()
        try:
            jax.block_until_ready(out)
        except TypeError:  # non-array output (host dict / python scalar)
            pass
        return out

    run_once()
    best = float("inf")
    for _ in range(max(repeat, 1)):
        t0 = time.perf_counter()
        run_once()
        best = min(best, time.perf_counter() - t0)
    return best


def _mode_thunk(spec: KernelSpec, mode: str, args, kwargs) -> Callable[[], Any]:
    if mode == MODE_REF:
        return lambda: spec.ref_fn(*args, **kwargs)
    return lambda: spec.pallas_fn(
        *args, interpret=(mode == MODE_INTERPRET), **kwargs)


def tune(
    cases: Iterable[Tuple[str, Sequence[Any], Dict[str, Any]]] = (),
    routes: Iterable[Tuple[str, Any, Dict[str, Callable[[], Any]]]] = (),
    *,
    repeat: int = 3,
    policy: Optional[DispatchPolicy] = None,
    path: Optional[str] = None,
    persist: bool = True,
    backend: Optional[str] = None,
) -> DispatchPolicy:
    """Microbenchmark autotuner: measure every runnable variant on the live
    backend and record the winners in a `DispatchPolicy`.

    cases   iterable of (kernel_name, args, kwargs): for each, every mode that
            can run here (ref everywhere; interpret when eligible; compiled
            pallas only on TPU) is timed and the fastest becomes the decision
            for that call's shape bucket.
    routes  iterable of (route_name, bucket, {candidate: thunk}): each thunk
            is timed as-is; the fastest candidate becomes the route decision
            (e.g. "packed"/"unpacked" prune routing).
    repeat  timing repeats per candidate (best-of, after a warmup run).
    policy  extend this policy instead of starting fresh; when omitted, an
            existing readable cache at the target path is loaded and
            extended — tune() never invalidates decisions it didn't re-measure
            (an unreadable/stale-schema cache is still replaced).
    path/persist  where (and whether) to save the JSON cache; the tuned
            policy is installed as the active one either way.

    A candidate that fails raises: a kernel that cannot run is a fault to
    fix, not a losing measurement.
    """
    be = backend or jax.default_backend()
    pol = policy
    if pol is None:
        target = path or policy_path()
        if os.path.exists(target):
            try:
                pol = DispatchPolicy.load(target)
            except (ValueError, KeyError, json.JSONDecodeError, OSError):
                pol = None  # unreadable cache: tune from scratch, overwrite
    if pol is None:
        pol = DispatchPolicy()
    pol.meta.update({
        "backend": be,
        "jax": jax.__version__,
        "repeat": int(repeat),
        "tuned_unix": time.time(),
    })

    for name, args, kwargs in cases:
        spec = get(name)
        if not spec.eligible(*args, **kwargs):
            continue  # ineligible shapes are always "ref"; nothing to decide
        bucket = spec.bucket(*args, **kwargs)
        measured: Dict[str, float] = {}
        for mode in _modes_runnable(be):
            measured[mode] = _time_thunk(
                _mode_thunk(spec, mode, args, kwargs), repeat)
        winner = min(measured, key=measured.get)
        pol.set_mode(name, be, bucket, winner, measured)

    # install the tuned kernel modes BEFORE timing routes: route thunks go
    # through dispatch(), so packed-vs-unpacked must be measured under the
    # kernel modes that will actually serve the winning route
    set_policy(pol)

    for name, bucket, candidates in routes:
        measured = {}
        for cand, thunk in candidates.items():
            measured[cand] = _time_thunk(thunk, repeat)
        winner = min(measured, key=measured.get)
        pol.set_route(name, be, bucket, winner, measured)

    if persist:
        pol.save(path)
    set_policy(pol)
    return pol
