"""graftsan: opt-in runtime sanitizers for the hazards graftlint can only
approximate statically.

Five sanitizers, enabled via
``PADDLE_TPU_SANITIZE=lock,recompile,hostsync,race,numerics`` (or
``all``) at process start, or programmatically with :func:`enable`:

- **lock** — a lock-order witness (the dynamic twin of GL007): the stack's
  known locks are wrapped so every acquisition-while-holding records an
  ordered edge; acquiring B while holding A after some thread acquired A
  while holding B raises :class:`LockOrderInversion` *before blocking*,
  naming both first-witness acquisition stacks. :func:`check_wait` is the
  dynamic GL004: a declared blocking wait (dataloader queue get) trips if
  the calling thread holds any sanitized lock.
- **recompile** — a recompile sentinel (the dynamic twin of GL008): the
  jit program caches (``jit/api.py`` to_static, ``jit/sot.py`` captures,
  the serving engine's prefill/decode caches) report every cache miss via
  :func:`note_compile`; more misses for one callable than the threshold
  (``PADDLE_TPU_SANITIZE_RECOMPILE_THRESHOLD``, default 8) raises
  :class:`RecompileStorm` with the recent signature history — the
  shape-varying-loop storm caught while it is still cheap.
- **hostsync** — a host-sync tripwire: a ``Tensor`` concretization
  (``.numpy()`` / ``.item()`` / ``float()`` …) inside an active
  ``trace.training_step`` / ``serving`` span — or any
  :func:`protected_region` — raises :class:`HostSyncInProtectedRegion`.
  Reads wrapped in :func:`allow_host_sync` are sanctioned.
- **race** — a data-race witness (the dynamic twin of GL010): instrumented
  hot classes (the serving engine's stats/span tables, FleetRouter,
  SLOTracker, CheckpointManager) report field accesses via
  :func:`race_access`; an Eraser-style candidate-lockset intersection over
  the SanitizedLock held-set per (owner, field) raises :class:`DataRace`
  when a mutated field's candidate set empties — both conflicting access
  stacks named, no lucky-timing crash required. Enabling ``race`` makes
  :func:`new_lock` return sanitized locks (held-set maintenance) even when
  the order witness is off.
- **numerics** — numsan, the runtime twin of graftir's GI005–GI007: one
  compiled device-side all-finite reduction over the registered step
  outputs at every step/burst boundary (:func:`numsan_check`), ONE bool
  to the host per step — no per-op sync. A non-finite value raises
  :class:`NumericsTrip` naming the step and the first non-finite region
  tag (the registered regions are re-checked in order to localize it);
  drilled via the ``numsan.check`` fault point. This replaces the old
  flag-gated per-op host NaN scanner on the hot paths; the eager
  per-op checker in ``amp/debugging.py`` remains for interactive
  debugging and now shares numsan's compiled check.

Discipline matches monitor/trace: **disabled by default**, every guard is
one slot load on a preallocated ``_state`` object, nothing is wrapped or
hooked until enabled — the tier-1 dispatch budget holds with sanitizers
off (tests/test_sanitizers.py).

Every trip also (best-effort) bumps
``paddle_tpu_monitor_sanitizer_trips_total``, records a
``monitor.sanitizer_trip`` span (``monitor.numsan_trip`` for numerics,
which carries the site/step/region attrs), and writes the trace
flight-recorder dump (the hang/post-mortem workflow of docs/tracing.md)
before raising.

This module is stdlib-only (no jax, no framework imports) like the rest of
``paddle_tpu.analysis``; runtime integration points import IT, and the
monitor/trace bindings — and numsan's jax half in ``numerics.py`` —
resolve lazily at trip/check time.
"""
from __future__ import annotations

import collections
import os
import threading
import traceback

__all__ = [
    "SanitizerError", "LockOrderInversion", "RecompileStorm",
    "HostSyncInProtectedRegion", "BlockingWaitUnderLock", "DataRace",
    "NumericsTrip",
    "enable", "disable", "enabled", "install_from_env", "reset",
    "SanitizedLock", "new_lock", "wrap_lock", "lock_order_edges",
    "check_wait",
    "note_compile", "compile_counts", "recompile_threshold",
    "set_recompile_threshold",
    "protected_region", "allow_host_sync", "trips",
    "race_access", "race_fields",
    "numsan_check", "numsan_counts",
]

_KINDS = ("lock", "recompile", "hostsync", "race", "numerics")


class SanitizerError(RuntimeError):
    """Base class: a graftsan sanitizer tripped."""


class LockOrderInversion(SanitizerError):
    """Two threads acquired the same two locks in opposite orders."""


class RecompileStorm(SanitizerError):
    """One callable crossed the compile-count threshold."""


class HostSyncInProtectedRegion(SanitizerError):
    """A device→host sync fired inside an active training/serving span."""


class BlockingWaitUnderLock(SanitizerError):
    """A declared blocking wait ran while holding a sanitized lock."""


class DataRace(SanitizerError):
    """An instrumented field's candidate lockset emptied while mutated —
    two threads touch it with no common lock."""


class NumericsTrip(SanitizerError):
    """A registered step-boundary region holds a non-finite value."""


class _State:
    """One slot load per guard when disabled — the monitor discipline.
    ``locktrack`` is the derived held-set-maintenance flag: on when the
    order witness OR the race witness needs to know which sanitized
    locks each thread holds."""

    __slots__ = ("on", "lock", "recompile", "hostsync", "race",
                 "numerics", "locktrack")

    def __init__(self):
        self.on = False
        self.lock = False
        self.recompile = False
        self.hostsync = False
        self.race = False
        self.numerics = False
        self.locktrack = False


_state = _state_singleton = _State()
_tls = threading.local()

# -- lock-order witness -------------------------------------------------------

_graph_lock = threading.Lock()
_edges = {}          # (held, acquired) -> first-witness stack (str)
_trips = []          # [(kind, message)] — test/postmortem introspection

# -- race witness -------------------------------------------------------------

_race_lock = threading.Lock()
_fields = {}         # (owner, field) -> _FieldAccess

# -- recompile sentinel -------------------------------------------------------

_recompile_lock = threading.Lock()
_compiles = {}       # label -> count
_signatures = {}     # label -> deque of recent signature reprs
_DEFAULT_THRESHOLD = 8
_threshold = [_DEFAULT_THRESHOLD]

# -- hostsync tripwire --------------------------------------------------------

_prev_hook = [None]
_hook_installed = [False]

# -- numerics sentinel --------------------------------------------------------

_numsan_lock = threading.Lock()
_numsan_counts = {}  # site -> device-side checks issued


def enabled(kind=None):
    """Whether any sanitizer (or one specific kind) is enabled."""
    if kind is None:
        return _state.on
    if kind not in _KINDS:
        raise ValueError(f"unknown sanitizer {kind!r} (known: {_KINDS})")
    return getattr(_state, kind)


def enable(*kinds):
    """Enable sanitizers (all five when called bare). Module-level monitor
    locks are wrapped now; locks constructed AFTER this call pick up
    wrapping via :func:`new_lock` at their construction sites."""
    kinds = kinds or _KINDS
    for k in kinds:
        if k not in _KINDS:
            raise ValueError(f"unknown sanitizer {k!r} (known: {_KINDS})")
        setattr(_state, k, True)
    _state.on = True
    _state.locktrack = _state.lock or _state.race
    if _state.locktrack:
        _wrap_known_locks()
    if _state.hostsync:
        _install_hook()


def disable(*kinds):
    """Disable sanitizers (all when called bare). Wrapped locks stay
    wrapped (they become pass-throughs: the guard slot is off)."""
    for k in (kinds or _KINDS):
        if k not in _KINDS:
            raise ValueError(f"unknown sanitizer {k!r} (known: {_KINDS})")
        setattr(_state, k, False)
    _state.on = (_state.lock or _state.recompile or _state.hostsync
                 or _state.race or _state.numerics)
    _state.locktrack = _state.lock or _state.race
    if not _state.hostsync:
        _uninstall_hook()


def install_from_env(env=None):
    """Enable from ``PADDLE_TPU_SANITIZE`` (comma list, ``all``, or ``1``);
    called once at package import. Returns the enabled kinds."""
    spec = (env if env is not None
            else os.environ.get("PADDLE_TPU_SANITIZE", "")).strip().lower()
    if not spec:
        return ()
    if spec in ("all", "1", "true", "on"):
        kinds = _KINDS
    else:
        kinds = tuple(k.strip() for k in spec.split(",") if k.strip())
        bad = [k for k in kinds if k not in _KINDS]
        if bad:
            import warnings

            warnings.warn(f"PADDLE_TPU_SANITIZE: unknown sanitizer(s) "
                          f"{bad}; known: {list(_KINDS)}", stacklevel=2)
            kinds = tuple(k for k in kinds if k in _KINDS)
    if kinds:
        enable(*kinds)
    thr = os.environ.get("PADDLE_TPU_SANITIZE_RECOMPILE_THRESHOLD")
    if thr:
        try:
            set_recompile_threshold(int(thr))
        except ValueError:
            pass
    return kinds


def reset():
    """Drop witnessed edges, compile counts and trip records (test
    isolation). Enable state is untouched."""
    with _graph_lock:
        _edges.clear()
    with _race_lock:
        _fields.clear()
    with _recompile_lock:
        _compiles.clear()
        _signatures.clear()
    with _numsan_lock:
        _numsan_counts.clear()
    del _trips[:]
    _tls.__dict__.clear()


def trips():
    """[(kind, message)] recorded by every trip so far."""
    return list(_trips)


# -- trip plumbing ------------------------------------------------------------

def _trip(exc_type, kind, message):
    """Record, export (metric + span + flight dump, all best-effort), then
    raise. The raise is the contract; the telemetry documents it."""
    _trips.append((kind, message))
    try:
        from .. import monitor as _m

        if _m._state.on:
            _m.counter("paddle_tpu_monitor_sanitizer_trips_total",
                       labelnames=("sanitizer",)).labels(kind).inc()
        t = _m.trace
        if t._state.on:
            now = _m.now_ns()
            t.record_span("monitor.sanitizer_trip", now, now,
                          attrs={"sanitizer": kind})
        if t._state.on or os.environ.get("PADDLE_TPU_FLIGHT_DIR"):
            t.flight_dump(reason=f"graftsan {kind} trip: {message[:300]}")
    except Exception:  # noqa: BLE001 — telemetry must not mask the trip
        pass
    raise exc_type(message)


# -- lock-order witness -------------------------------------------------------

def _held():
    st = getattr(_tls, "held", None)
    if st is None:
        st = _tls.held = []
    return st


class SanitizedLock:
    """Thin proxy over a real lock that feeds the order witness. The inner
    lock keeps the blocking semantics; the witness only reads/writes the
    per-thread held list and the (tiny) process-wide edge map. Stacks are
    captured ONLY when a new edge is first witnessed, so steady-state
    acquisition cost is a list append."""

    __slots__ = ("name", "_inner")

    def __init__(self, name, inner=None):
        self.name = name
        self._inner = inner if inner is not None else threading.Lock()

    def acquire(self, blocking=True, timeout=-1):
        if _state.lock:
            self._witness()
        ok = self._inner.acquire(blocking, timeout)
        if ok and _state.locktrack:
            # the race witness reads this held-set too, so maintenance
            # stays on whenever either consumer is enabled
            _held().append(self.name)
        return ok

    def _witness(self):
        """Record held→this edges; trip on a known reverse edge BEFORE
        blocking (the reproducer raises instead of deadlocking)."""
        held = _held()
        if not held:
            return
        trip_msg = None
        with _graph_lock:
            for h in held:
                if h == self.name:
                    continue
                rev = _edges.get((self.name, h))
                if rev is not None:
                    here = "".join(traceback.format_stack(limit=12))
                    trip_msg = (
                        f"lock-order inversion: this thread holds '{h}' and "
                        f"is acquiring '{self.name}', but the opposite "
                        f"order '{self.name}' -> '{h}' was already "
                        "witnessed — a deadlock under the right "
                        "interleaving.\n"
                        f"-- first witness of {self.name} -> {h}:\n{rev}\n"
                        f"-- this acquisition of {h} -> {self.name}:\n"
                        f"{here}")
                    break
                if (h, self.name) not in _edges:
                    _edges[(h, self.name)] = "".join(
                        traceback.format_stack(limit=12))
        if trip_msg is not None:
            _trip(LockOrderInversion, "lock", trip_msg)

    def release(self):
        self._inner.release()
        # pop unconditionally: a disable() between another thread's acquire
        # and its release must not leak a phantom held entry that causes
        # false trips after the next enable (no-op when the name is absent)
        held = _held()
        for i in range(len(held) - 1, -1, -1):
            if held[i] == self.name:
                del held[i]
                break

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def locked(self):
        return self._inner.locked()

    def __repr__(self):
        return f"SanitizedLock({self.name!r}, {self._inner!r})"


def new_lock(name, factory=threading.Lock):
    """A lock for construction sites on the sanitizer's known-lock list
    (watchdog, registry): sanitized when the lock sanitizer is on at
    construction, a plain lock (zero overhead) otherwise."""
    inner = factory()
    return SanitizedLock(name, inner) if _state.locktrack else inner


def wrap_lock(name, lock):
    """Wrap an existing lock (module-level monitor/trace locks at
    enable time). Idempotent."""
    if isinstance(lock, SanitizedLock):
        return lock
    return SanitizedLock(name, lock)


def lock_order_edges():
    """Snapshot of witnessed ordered edges: {(held, acquired): stack}."""
    with _graph_lock:
        return dict(_edges)


def check_wait(site):
    """Declare an about-to-block wait (queue get, join). Trips when the
    calling thread holds any sanitized lock — the dynamic form of GL004."""
    if not _state.lock:
        return
    held = _held()
    if held:
        _trip(BlockingWaitUnderLock, "lock",
              f"blocking wait at {site} while holding {held} — every "
              "other thread touching the lock(s) convoys behind this "
              "wait; move it outside the critical section")


def _wrap_known_locks():
    """Swap the module-level monitor/trace/obs-server locks for sanitized
    proxies. Instrument sites reference the module globals by name, so the
    swap takes effect everywhere at once. Lazy: pulls in the monitor
    package (already imported in any running process). Instance locks in
    the fleet/checkpoint tier (FleetRouter, SLOTracker, per-metric
    Registry locks, the checkpoint writer's error lock) are constructed
    through :func:`new_lock` and pick up wrapping at construction — enable
    sanitizers before building the objects you want witnessed."""
    try:
        from .. import monitor as _m
        from ..monitor import trace as _t

        _t._open_lock = wrap_lock("monitor.trace._open_lock", _t._open_lock)
        _m._sample_lock = wrap_lock("monitor._sample_lock", _m._sample_lock)
        # the default Registry is constructed at package import, BEFORE an
        # env-driven enable runs — wrap its (held-across-construction-and-
        # snapshot) lock here; per-metric locks created after enable pick
        # up wrapping via new_lock at their construction sites
        _m.registry._lock = wrap_lock("monitor.registry.Registry",
                                      _m.registry._lock)
    except Exception:  # noqa: BLE001 — partial bootstrap must not fail
        pass
    try:
        # the obs-server module lock guards the scrape/statusz section
        # registry from request-handler threads (also import-time state)
        from ..monitor import server as _srv

        _srv._lock = wrap_lock("monitor.server._lock", _srv._lock)
    except Exception:  # noqa: BLE001
        pass


# -- race witness -------------------------------------------------------------

class _FieldAccess:
    """Eraser state for one (owner, field): ``exclusive`` while a single
    thread touches it (initialization), then ``shared``/``shared_mod``
    with a candidate lockset that intersects toward the truth."""

    __slots__ = ("state", "tid", "written", "lockset", "stack",
                 "stack_locks", "tripped")

    def __init__(self, tid, written):
        self.state = "exclusive"
        self.tid = tid
        self.written = written
        self.lockset = None         # TOP until a second thread arrives
        self.stack = None           # first conflicting-access stack
        self.stack_locks = None
        self.tripped = False


def race_access(owner, field, write=False):
    """One access to an instrumented shared field. ``owner`` names the
    instance (the engine's ``_san_tag``, ``fleet.<tag>``), ``field`` the
    attribute. Per (owner, field), the candidate lockset starts at TOP
    during single-threaded initialization and intersects with the
    caller's sanitized-lock held-set on every access once a second
    thread arrives (Eraser). An empty candidate set on a written field
    raises :class:`DataRace` naming BOTH conflicting stacks — the first
    cross-thread access and this one. Disabled cost: one slot load."""
    if not _state.race:
        return
    held = frozenset(_held())
    me = threading.get_ident()
    trip = None
    with _race_lock:
        fa = _fields.get((owner, field))
        if fa is None:
            _fields[(owner, field)] = _FieldAccess(me, write)
            return
        if fa.state == "exclusive" and fa.tid == me:
            fa.written = fa.written or write
            return
        if fa.state == "exclusive":
            # second thread: initialization is over, constraints begin
            fa.state = "shared_mod" if (write or fa.written) else "shared"
            fa.lockset = set(held)
            fa.stack = "".join(traceback.format_stack(limit=12))
            fa.stack_locks = held
        else:
            fa.lockset &= held
            if write and fa.state == "shared":
                fa.state = "shared_mod"
        if fa.state == "shared_mod" and not fa.lockset \
                and not fa.tripped:
            fa.tripped = True     # one report per field, not a cascade
            here = "".join(traceback.format_stack(limit=12))
            trip = (
                f"data race on '{field}' of '{owner}': the candidate "
                "lockset is EMPTY for a written shared field — no "
                "single lock is held at every access, so two threads "
                "can interleave on it.\n"
                f"-- first cross-thread access (held "
                f"{sorted(fa.stack_locks or ())}):\n{fa.stack}\n"
                f"-- this access (held {sorted(held)}):\n{here}")
    if trip is not None:
        _trip(DataRace, "race", trip)


def race_fields():
    """Snapshot: {(owner, field): (state, sorted candidate locks|None)}
    for every instrumented field seen while enabled."""
    with _race_lock:
        return {k: (fa.state,
                    None if fa.lockset is None else sorted(fa.lockset))
                for k, fa in _fields.items()}


# -- recompile sentinel -------------------------------------------------------

def recompile_threshold():
    return _threshold[0]


def set_recompile_threshold(n):
    n = int(n)
    if n < 1:
        raise ValueError("recompile threshold must be >= 1")
    _threshold[0] = n


def note_compile(label, signature=None):
    """One program-cache miss for ``label``. Called by jit/api.py,
    jit/sot.py and the serving engine's jit caches — guarded at the call
    site on ``_state.recompile`` so the disabled cost is one slot load."""
    if not _state.recompile:
        return
    trip_msg = None
    with _recompile_lock:
        c = _compiles.get(label, 0) + 1
        _compiles[label] = c
        sigs = _signatures.get(label)
        if sigs is None:
            sigs = _signatures[label] = collections.deque(maxlen=8)
        if signature is not None:
            sigs.append(str(signature)[:200])
        if c == _threshold[0] + 1:
            recent = "\n  ".join(sigs) or "<signatures not reported>"
            trip_msg = (
                f"recompile storm: '{label}' compiled {c} times "
                f"(threshold {_threshold[0]}). Each miss pays a full "
                "trace+XLA compile. Shape-varying inputs? Pad or bucket "
                "them; unhashable/per-call static args? Hoist them. "
                f"Recent signatures:\n  {recent}")
    if trip_msg is not None:
        _trip(RecompileStorm, "recompile", trip_msg)


def compile_counts():
    """Snapshot: {label: cache-miss count} recorded while enabled."""
    with _recompile_lock:
        return dict(_compiles)


# -- hostsync tripwire --------------------------------------------------------

class _Region:
    __slots__ = ("name",)

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        st = getattr(_tls, "regions", None)
        if st is None:
            st = _tls.regions = []
        st.append(self.name)
        return self

    def __exit__(self, *exc):
        st = getattr(_tls, "regions", None)
        if st:
            st.pop()
        return False


def protected_region(name):
    """Mark a host-code region (serving step, custom training loop) in
    which a Tensor device→host sync is a bug. Nestable, per-thread."""
    return _Region(name)


class _Allow:
    __slots__ = ()

    def __enter__(self):
        _tls.allow = getattr(_tls, "allow", 0) + 1
        return self

    def __exit__(self, *exc):
        _tls.allow = max(0, getattr(_tls, "allow", 1) - 1)
        return False


def allow_host_sync():
    """Sanction an intentional sync inside a protected region (metrics
    readout, debugging)."""
    return _Allow()


_PROTECTED_PREFIXES = ("train", "serving")


def _active_protected_region():
    st = getattr(_tls, "regions", None)
    if st:
        return st[-1]
    try:
        from ..monitor import trace as _t

        if _t._state.on:
            for sp in reversed(_t.thread_span_stack()):
                if sp.name.split(".", 1)[0] in _PROTECTED_PREFIXES:
                    return sp.name
    except Exception:  # noqa: BLE001
        return None
    return None


def _concretize_tripwire(t):
    if _state.hostsync and not getattr(_tls, "allow", 0):
        region = _active_protected_region()
        if region is not None:
            _trip(HostSyncInProtectedRegion, "hostsync",
                  f"device->host sync (Tensor concretization) inside "
                  f"active span '{region}': a hidden round-trip "
                  "serializes the async dispatch pipeline. Hoist the "
                  "read out of the hot region, keep the reduction on "
                  "device, or wrap an intentional read in "
                  "sanitizers.allow_host_sync().")
    prev = _prev_hook[0]
    # never chain to ourselves: a disable() landing inside SOT's temporary
    # hook swap (jit/sot.py capture) leaves the tripwire in the slot after
    # SOT restores it, and the next enable() would otherwise save it as
    # its own prev — infinite recursion on every .numpy()
    if prev is not None and prev is not _concretize_tripwire:
        prev(t)


def _install_hook():
    """Chain the tripwire into the framework's concretization hook slot
    (framework/core.py ``_CONCRETIZE_HOOK``). Install only while enabled:
    the disabled process keeps its bare None slot (zero cost). SOT's
    cold-run recorder swaps the slot for the duration of a capture — host
    reads there are the graph-break mechanism, not a bug — and restores it
    after."""
    if _hook_installed[0]:
        return
    try:
        from ..framework import core as _core
    except Exception:  # noqa: BLE001 — analysis-only venv: no runtime hook
        return
    prev = _core._CONCRETIZE_HOOK[0]
    # the slot may still hold the tripwire (uninstall raced SOT's capture
    # swap, see _concretize_tripwire) — a stale self-reference must not
    # become our prev
    _prev_hook[0] = None if prev is _concretize_tripwire else prev
    _core._CONCRETIZE_HOOK[0] = _concretize_tripwire
    _hook_installed[0] = True


def _uninstall_hook():
    if not _hook_installed[0]:
        return
    try:
        from ..framework import core as _core
    except Exception:  # noqa: BLE001
        return
    if _core._CONCRETIZE_HOOK[0] is _concretize_tripwire:
        _core._CONCRETIZE_HOOK[0] = _prev_hook[0]
    _prev_hook[0] = None
    _hook_installed[0] = False


# -- numerics sentinel (numsan) -----------------------------------------------

def numsan_check(site, regions, step=None):
    """One device-side all-finite check over ``regions`` at a step/burst
    boundary. ``regions`` is ``((tag, pytree), ...)`` — the step's
    committed outputs (serving tokens + KV pools, the mesh step's loss /
    params / optimizer state), in the order the bisection should report
    them. Callers guard on ``_state.numerics`` so the disabled cost is
    one slot load; the enabled cost is one compiled reduction and ONE
    bool to the host per step (a raw jax.Array read, not a Tensor
    concretization — it cannot cross the hostsync tripwire).

    The ``numsan.check`` fault point drills the path: armed with
    ``action="flag"``, the check sees region ``seed % len(regions)``
    with one extra NaN leaf appended host-side — the engine's values are
    never touched, so step outputs stay bit-exact whether or not the
    drill (or numsan itself) is on.
    """
    if not _state.numerics:
        return
    regions = tuple(regions)
    if not regions:
        return
    from . import faultinject as _fi
    from . import numerics as _num

    spec = _fi.fire("numsan.check")
    if spec is not None:
        k = spec.seed % len(regions)
        tag, tree = regions[k]
        regions = (regions[:k] + ((tag, _num.poisoned(tree)),)
                   + regions[k + 1:])
    with _numsan_lock:
        _numsan_counts[site] = _numsan_counts.get(site, 0) + 1
    try:
        from .. import monitor as _m

        if _m._state.on:
            _m.counter("paddle_tpu_monitor_numsan_checks_total",
                       labelnames=("site",)).labels(site).inc()
    except Exception:  # noqa: BLE001 — telemetry must not break the check
        pass
    if _num.all_finite(tuple(t for _, t in regions)):
        return
    bad = _num.first_bad_region(regions)
    at = f"step {step}" if step is not None else "an untracked step"
    msg = (f"non-finite value at {site} ({at}): first non-finite region "
           f"is '{bad or '<combined check only>'}' of "
           f"{[t for t, _ in regions]} — a NaN/inf crossed the step "
           "boundary; replay under the eager checker "
           "(amp.debugging.enable_tensor_checker) to name the op, or "
           "run the GI006 hazard report for the static candidates")
    _numsan_trip(site, step, bad, msg)


def numsan_counts():
    """Snapshot: {site: device-side checks issued} while enabled."""
    with _numsan_lock:
        return dict(_numsan_counts)


def _numsan_trip(site, step, region, message):
    """The numerics flavor of :func:`_trip`: same record/export/raise
    contract, but the span is ``monitor.numsan_trip`` carrying the
    site/step/region the bisection localized."""
    _trips.append(("numerics", message))
    try:
        from .. import monitor as _m

        if _m._state.on:
            _m.counter("paddle_tpu_monitor_sanitizer_trips_total",
                       labelnames=("sanitizer",)).labels("numerics").inc()
        t = _m.trace
        if t._state.on:
            now = _m.now_ns()
            t.record_span("monitor.numsan_trip", now, now,
                          attrs={"site": site,
                                 "step": "?" if step is None
                                 else str(step),
                                 "region": region or "?"})
        if t._state.on or os.environ.get("PADDLE_TPU_FLIGHT_DIR"):
            t.flight_dump(
                reason=f"graftsan numerics trip: {message[:300]}")
    except Exception:  # noqa: BLE001 — telemetry must not mask the trip
        pass
    raise NumericsTrip(message)
