"""The device seam: everything between the scheduler and ``jax``.

- ``traced_jit``: the ``jax.jit`` wrapper every device kernel uses —
  trace counting against a declared retrace budget, the kernel registry
  the jaxpr analyzer re-traces from, per-kernel call/compile profiling,
  and the watchdog/breaker guard around each dispatch.
- the persistent compile cache placement (``configure_compile_cache``).
- the mesh sharding seam (``get_mesh`` / ``shard_put``).

The program runs on whatever backend jax initialises. Nothing here
probes for a backend, falls back to another one, or starts a process:
a chip belongs to one process at a time, so a parent that has touched
jax must not spawn a child that needs the device.
"""

import functools
import os
import threading
import time

# -- persistent compile cache --------------------------------------------------
#
# Every cold process otherwise recompiles every shape bucket. The cache
# directory is placeable from outside: where ``JAX_COMPILATION_CACHE_DIR``
# is set jax already reads it and the code names no other directory;
# otherwise the cache lives at ONE fixed path inside the checkout (a
# directory that moves between runs never hits). The thresholds drop to
# zero so every placement kernel is stored, however fast it compiled.

COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)

_cache_lock = threading.Lock()
_cache_configured = False
_cache_events = {"hits": 0, "misses": 0}


_CACHE_EVENT_KEYS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}


def _on_jax_event(event: str, **_kwargs) -> None:
    key = _CACHE_EVENT_KEYS.get(event)
    if key is not None:
        with _cache_lock:  # compiles run on several watchdog threads
            _cache_events[key] += 1


def configure_compile_cache() -> None:
    """Place jax's persistent compilation cache (idempotent; called
    before the first ``jax.jit`` by ``traced_jit`` and the test rig)."""
    global _cache_configured
    with _cache_lock:
        if _cache_configured:
            return
        _cache_configured = True
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.monitoring.register_event_listener(_on_jax_event)


def compile_cache_stats() -> dict:
    """Where the persistent cache lives and how many compile requests
    it answered (hits) or had to compile and store (misses)."""
    import jax

    with _cache_lock:
        return {"dir": jax.config.jax_compilation_cache_dir, **_cache_events}


# -- jit trace accounting ----------------------------------------------------
#
# ``traced_jit`` is the seam the retrace budget checker
# (nomad_tpu.analysis.retrace) reads: it wraps a kernel's Python body with
# a counter bump BEFORE handing it to jax.jit, so the counter increments
# exactly once per XLA trace (jit only re-executes the Python body on a
# cache miss) and never on a cached dispatch. A hot-path kernel that
# silently retraces per call — a dropped shape bucket, a static arg that
# became dynamic — shows up as a counter marching in lockstep with the
# call count instead of plateauing at the handful of shape buckets its
# declared budget allows.

_trace_lock = threading.Lock()
_trace_counts: dict[str, int] = {}
_trace_budgets: dict[str, int] = {}

# -- kernel registry (nomad_tpu.analysis.jaxlint) -----------------------------
#
# Every ``traced_jit`` decoration registers a ``KernelEntry``: the
# ORIGINAL un-jitted body, the jit kwargs (static_argnames included),
# and — recorded at trace time, when the dynamic args are tracers
# carrying avals and the static args are plain Python values — the
# last-seen abstract call specs. The jaxpr analyzer re-traces each
# registered kernel from these specs with ``jax.make_jaxpr`` and walks
# the resulting ClosedJaxpr, so purity/dtype/determinism/fingerprint
# invariants are checked against the *traced program*, not the Python
# source.

_KERNEL_SPECS_MAX = 8  # distinct abstract call specs kept per kernel


class KernelEntry:
    """One registered device kernel: identity, jit config, and the
    abstract call specs seen so far (newest last)."""

    __slots__ = ("name", "short", "fn", "jit_kwargs", "retrace_budget",
                 "specs")

    def __init__(self, name, short, fn, jit_kwargs, retrace_budget):
        self.name = name
        self.short = short
        self.fn = fn
        self.jit_kwargs = dict(jit_kwargs)
        self.retrace_budget = retrace_budget
        # sig string -> {"args": [spec...], "kwargs": {name: spec}};
        # insertion-ordered, bounded to _KERNEL_SPECS_MAX (oldest evicted)
        self.specs: dict[str, dict] = {}

    @property
    def static_argnames(self) -> tuple:
        sa = self.jit_kwargs.get("static_argnames", ())
        return (sa,) if isinstance(sa, str) else tuple(sa)

    def last_spec(self):
        """Newest recorded abstract call spec, or None if never traced."""
        if not self.specs:
            return None
        return next(reversed(self.specs.values()))

    def describe(self) -> dict:
        return {
            "name": self.name,
            "short": self.short,
            "module": self.fn.__module__,
            "qualname": self.fn.__qualname__,
            "static_argnames": list(self.static_argnames),
            "retrace_budget": self.retrace_budget,
            "specs": list(self.specs),
        }


_kernel_registry: dict[str, KernelEntry] = {}


def _is_static(a) -> bool:
    """A plain Python value a static argument can be rebuilt from;
    tuples of such values (an operand layout) included."""
    if isinstance(a, tuple):
        return all(_is_static(v) for v in a)
    return a is None or isinstance(a, (bool, int, float, str))


def _arg_spec(a):
    """Abstract spec of one kernel argument, built at trace time.

    Dynamic args are tracers -> ("aval", shape, dtype, weak_type);
    static args are plain Python values or tuples of them ->
    ("static", value); anything
    the analyzer cannot reconstruct -> ("opaque", type name)."""
    aval = getattr(a, "aval", None)
    if aval is not None and hasattr(aval, "shape"):
        return ("aval", tuple(int(d) for d in aval.shape),
                str(aval.dtype), bool(getattr(aval, "weak_type", False)))
    if _is_static(a):
        return ("static", a)
    if hasattr(a, "shape") and hasattr(a, "dtype"):  # concrete array
        return ("aval", tuple(int(d) for d in a.shape),
                str(a.dtype), False)
    return ("opaque", type(a).__name__)


def _record_kernel_spec(name: str, sig: str, args, kwargs) -> None:
    """Record the abstract call spec under ``sig`` (called from the
    trace-time counter, so once per XLA trace, never per dispatch)."""
    entry = _kernel_registry.get(name)
    if entry is None:
        return
    spec = {
        "args": [_arg_spec(a) for a in args],
        "kwargs": {k: _arg_spec(v) for k, v in sorted(kwargs.items())},
    }
    entry.specs.pop(sig, None)
    entry.specs[sig] = spec
    while len(entry.specs) > _KERNEL_SPECS_MAX:
        entry.specs.pop(next(iter(entry.specs)))


def kernel_registry() -> dict[str, KernelEntry]:
    """Snapshot of the registered kernel fleet (name -> KernelEntry).
    Entries are live objects — the analyzer reads, never mutates."""
    with _trace_lock:
        return dict(_kernel_registry)

# -- kernel profiling (nomad_tpu.obs) ----------------------------------------
#
# Per-kernel call/compile accounting behind the same lock: every
# traced_jit call records its dispatch wall time (the ``.dispatch`` sample
# and the ``kernel:<name>`` span); calls that triggered an
# XLA trace additionally record the abstract batch shape that caused it
# and land in a bounded recent-events list. Caveat, stated honestly:
# dispatch wall time UNDERESTIMATES device execute time under jax's
# async dispatch (we deliberately do not block_until_ready — profiling
# must not change the pipeline), while a trace-triggering call's wall
# time INCLUDES trace+compile, which is why those are exported as a
# separate ``.compile`` sample series.

_KERNEL_TRACE_EVENTS = 32  # recent trace events kept per kernel

_kernel_stats: dict[str, dict] = {}
_kernel_traces: dict[str, list[dict]] = {}
_last_trace_shape: dict[str, str] = {}

_obs_tracer = None  # lazily bound nomad_tpu.obs.trace.global_tracer


def record_trace(name: str) -> None:
    with _trace_lock:
        _trace_counts[name] = _trace_counts.get(name, 0) + 1


def _shape_sig(args, kwargs) -> str:
    """Abstract signature of a kernel call — built only at trace time,
    when the positional args are jax tracers carrying shape/dtype."""
    parts = []
    for a in list(args) + [v for _, v in sorted(kwargs.items())]:
        shp = getattr(a, "shape", None)
        if shp is not None:
            dt = getattr(getattr(a, "dtype", None), "name", "?")
            parts.append(f"{dt}[{','.join(str(d) for d in shp)}]")
        elif isinstance(a, (bool, int, float, str)):
            parts.append(repr(a))
    return " ".join(parts)[:256]


def _record_kernel_call(
    name: str, short: str, start: float, seconds: float, traced: bool
) -> None:
    with _trace_lock:
        st = _kernel_stats.setdefault(
            name, {"calls": 0, "traces": 0, "total_s": 0.0}
        )
        st["calls"] += 1
        st["total_s"] += seconds
        shape = _last_trace_shape.get(name, "")
        if traced:
            st["traces"] += 1
            events = _kernel_traces.setdefault(name, [])
            events.append({"shape": shape, "wall_s": round(seconds, 6)})
            del events[:-_KERNEL_TRACE_EVENTS]
    from .metrics import global_metrics

    global_metrics.measure(
        f"nomad.kernel.{short}.compile" if traced
        else f"nomad.kernel.{short}.dispatch",
        seconds,
    )
    global _obs_tracer
    if _obs_tracer is None:
        from ..obs.trace import global_tracer

        _obs_tracer = global_tracer
    _obs_tracer.record_kernel(
        short, seconds, start=start, traced=traced,
        shape=shape if traced else None,
    )


def kernel_profile() -> dict:
    """Per-kernel profile snapshot: call/trace counts, cumulative wall
    time, the last shapes that triggered traces (the /v1/agent/trace
    ``kernels`` section and the retrace post-mortem companion)."""
    with _trace_lock:
        out = {}
        for name, st in _kernel_stats.items():
            out[name] = {
                "calls": st["calls"],
                "traces": st["traces"],
                "total_ms": round(st["total_s"] * 1000.0, 3),
                "last_trace_shape": _last_trace_shape.get(name, ""),
                "recent_traces": list(_kernel_traces.get(name, ())),
            }
        return out


def reset_kernel_profile() -> None:
    with _trace_lock:
        _kernel_stats.clear()
        _kernel_traces.clear()
        _last_trace_shape.clear()


def trace_counts() -> dict[str, int]:
    with _trace_lock:
        return dict(_trace_counts)


def trace_budgets() -> dict[str, int]:
    with _trace_lock:
        return dict(_trace_budgets)


def reset_trace_counts() -> None:
    with _trace_lock:
        for k in _trace_counts:
            _trace_counts[k] = 0


_reference_tls = threading.local()  # .active: inside a _reference_call


def traced_jit(fn=None, *, trace_name=None, retrace_budget=None, **jit_kwargs):
    """Drop-in ``jax.jit`` replacement that counts traces per callable and
    (optionally) declares a retrace budget for the analysis checker::

        @functools.partial(traced_jit, retrace_budget=16,
                           static_argnames=("max_j", "k"))
        def place_kernel(...): ...

    jax is imported lazily at decoration time, so importing this module
    stays safe in jax-free contexts."""
    if fn is None:
        return functools.partial(
            traced_jit,
            trace_name=trace_name,
            retrace_budget=retrace_budget,
            **jit_kwargs,
        )
    import jax

    configure_compile_cache()
    name = trace_name or f"{fn.__module__}.{fn.__qualname__}"
    short = name.rsplit(".", 1)[-1]
    with _trace_lock:
        _trace_counts.setdefault(name, 0)
        if retrace_budget is not None:
            _trace_budgets[name] = retrace_budget
        _kernel_registry[name] = KernelEntry(
            name, short, fn, jit_kwargs, retrace_budget
        )

    @functools.wraps(fn)
    def _counted(*args, **kwargs):
        record_trace(name)
        sig = _shape_sig(args, kwargs)
        with _trace_lock:
            _last_trace_shape[name] = sig
            _record_kernel_spec(name, sig, args, kwargs)
        return fn(*args, **kwargs)

    jitted = jax.jit(_counted, **jit_kwargs)
    watchdog_on = os.environ.get("NOMAD_TPU_KERNEL_WATCHDOG", "1") != "0"

    def _reference_call(args, kwargs):
        """The exact CPU/reference path: the ORIGINAL un-jitted body,
        op by op, inputs pulled to host and computation pinned to the
        CPU backend so a sick device is never consulted. Eager jax ops
        and the jitted program compute the same values; with the whole
        pass on this path the placements are byte-identical to a
        from-scratch CPU run."""
        from .metrics import global_metrics

        t0 = time.perf_counter()
        try:
            cpu = jax.local_devices(backend="cpu")[0]
        except Exception:
            cpu = None

        def _host(x):
            if hasattr(x, "shape") and hasattr(x, "dtype") and hasattr(
                x, "__array__"
            ):
                try:
                    import numpy as np

                    return np.asarray(x)
                except Exception:
                    return x
            return x

        args = tuple(_host(a) for a in args)
        kwargs = {k: _host(v) for k, v in kwargs.items()}
        # a kernel this body calls runs its own body here too: its
        # breaker may be closed, and its dispatch would leave for the
        # watchdog's thread, where the CPU pin below does not hold
        outer = getattr(_reference_tls, "active", False)
        _reference_tls.active = True
        try:
            if cpu is not None:
                with jax.default_device(cpu):
                    out = fn(*args, **kwargs)
            else:
                out = fn(*args, **kwargs)
        finally:
            _reference_tls.active = outer
        global_metrics.incr("nomad.resilience.fallback_calls")
        global_metrics.measure(
            f"nomad.kernel.{short}.fallback", time.perf_counter() - t0
        )
        return out

    @functools.wraps(fn)
    def _profiled(*args, **kwargs):
        from ..chaos.plane import chaos_site
        from ..resilience.breaker import breaker_for
        from ..resilience.errors import KernelDeadlineExceeded

        # nested kernel: when an outer traced_jit kernel is being traced
        # and calls this one, the args are tracers bound to the caller's
        # thread-local trace — shipping them to the watchdog thread leaks
        # them. The outer call's breaker/watchdog already covers the
        # whole fused computation, so just inline.
        if any(
            isinstance(leaf, jax.core.Tracer)
            for leaf in jax.tree_util.tree_leaves((args, kwargs))
        ):
            return jitted(*args, **kwargs)
        if getattr(_reference_tls, "active", False):
            return fn(*args, **kwargs)  # inside an outer reference call
        br = breaker_for(name)
        if not br.allow():
            return _reference_call(args, kwargs)
        # a raise here models a device-side failure (OOM, preempted
        # TPU); the worker's batch path falls back to single-eval runs
        try:
            chaos_site("kernel.execute")
        except Exception as e:
            br.record_failure(e)
            raise
        before = _trace_counts.get(name, 0)

        def _thunk():
            # a hang here models a wedged PJRT call — only the watchdog
            # deadline gets the caller's thread back
            chaos_site("kernel.hang")
            return jitted(*args, **kwargs)

        t0 = time.perf_counter()
        try:
            if watchdog_on and br.execute_deadline > 0:
                from ..resilience.watchdog import global_executor

                out = global_executor.run(
                    _thunk,
                    name=name,
                    deadline_s=br.execute_deadline,
                    extend_deadline_s=br.compile_deadline,
                    extend_probe=(
                        lambda: _trace_counts.get(name, 0) > before
                    ),
                )
            else:
                out = _thunk()
        except KernelDeadlineExceeded as e:
            br.record_timeout(e)
            # finish THIS call on the reference path: a mid-batch trip
            # must not fail sibling members of the merged commit
            return _reference_call(args, kwargs)
        except Exception as e:
            br.record_failure(e)
            raise
        br.record_success()
        dt = time.perf_counter() - t0
        _record_kernel_call(
            name, short, t0, dt, _trace_counts.get(name, 0) > before
        )
        return out

    _profiled.jitted = jitted  # escape hatch: the raw jax.jit object
    return _profiled


# -- mesh sharding seam -------------------------------------------------------
#
# The ONE place the repo constructs a jax Mesh / NamedSharding and calls
# jax.device_put on pipeline tensors (NTA015 bans it elsewhere in
# device/ and scheduler/). Axis names match tests/test_mesh_sharding.py:
# "groups" is data-parallel over the eval/group axis, "nodes" shards the
# node axis region-major. The degenerate 1x1 mesh keeps get_mesh()
# callable everywhere while leaving the single-device jaxpr — and thus
# placements — bit-identical.

_MESH_ENV = "NOMAD_TPU_MESH"

_mesh_lock = threading.Lock()
_mesh_config = None  # cached MeshConfig | None (None = not resolved yet)
_shard_drops: dict[str, int] = {}  # see shard_drops()


class MeshConfig:
    """Resolved mesh decision. ``mesh`` is a ``jax.sharding.Mesh`` when
    ``active``, else None; ``dp``/``mp`` are the groups/nodes axis sizes
    (1,1 when degenerate)."""

    __slots__ = ("mesh", "dp", "mp", "source")

    def __init__(self, mesh, dp: int, mp: int, source: str):
        self.mesh = mesh
        self.dp = int(dp)
        self.mp = int(mp)
        self.source = source

    @property
    def active(self) -> bool:
        return self.mesh is not None

    @property
    def n_node_shards(self) -> int:
        return self.mp if self.mesh is not None else 1

    def describe(self) -> dict:
        """The self-describing ``mesh`` block of ``chip_smoke.py``'s
        report (``mesh_report`` adds which devices hold the shards)."""
        return {
            "active": self.active,
            "shape": [self.dp, self.mp],
            "axis_names": ["groups", "nodes"],
            "source": self.source,
        }


def parse_mesh_spec(spec: str):
    """``NOMAD_TPU_MESH`` grammar: ``off``/``0`` (degenerate), ``auto``
    (shape from all visible devices), or ``dp,mp``. Returns "off",
    "auto", or an (dp, mp) int tuple; raises ValueError on junk."""
    s = (spec or "").strip().lower()
    if s in ("off", "0", "none"):
        return "off"
    if s == "auto":
        return "auto"
    parts = s.split(",")
    if len(parts) != 2:
        raise ValueError(
            f"bad {_MESH_ENV}={spec!r}: expected 'dp,mp', 'auto', or 'off'"
        )
    dp, mp = int(parts[0]), int(parts[1])
    if dp < 1 or mp < 1:
        raise ValueError(f"bad {_MESH_ENV}={spec!r}: axes must be >= 1")
    if mp & (mp - 1):
        raise ValueError(
            f"bad {_MESH_ENV}={spec!r}: nodes axis must be a power of two "
            "(it must divide the padded node bucket)"
        )
    return (dp, mp)


def auto_mesh_shape(n_devices: int) -> tuple[int, int]:
    """Shape rule for ``auto``: use the largest power-of-two device
    count, cap the node axis at 8 (the minimum node bucket), put the
    rest on the groups axis. 8 devices -> (2, 4)."""
    total = 1
    while total * 2 <= n_devices:
        total *= 2
    if total <= 1:
        return (1, 1)
    mp = min(8, total // 2) if total > 2 else total
    dp = total // mp
    return (dp, mp)


def _resolve_mesh() -> "MeshConfig":
    spec = os.environ.get(_MESH_ENV)
    if spec is None:
        # Unset: activate automatically only on a real accelerator
        # backend with >1 device — the production default. The CPU test
        # rig (8 virtual host devices) stays degenerate unless a test
        # opts in, so the single-device jaxpr suite is undisturbed.
        import jax

        if jax.default_backend() == "cpu" or len(jax.devices()) <= 1:
            return MeshConfig(None, 1, 1, "default-off")
        parsed = "auto"
        source = "auto-detected"
    else:
        parsed = parse_mesh_spec(spec)
        source = f"env:{spec.strip()}"
    if parsed == "off":
        return MeshConfig(None, 1, 1, source)
    import jax

    devices = jax.devices()
    if parsed == "auto":
        dp, mp = auto_mesh_shape(len(devices))
    else:
        dp, mp = parsed
    if dp * mp > len(devices):
        raise ValueError(
            f"{_MESH_ENV} asks for {dp}x{mp}={dp * mp} devices but only "
            f"{len(devices)} are visible"
        )
    if dp * mp == 1:
        return MeshConfig(None, 1, 1, source)
    import numpy as _np
    from jax.sharding import Mesh

    grid = _np.array(devices[: dp * mp]).reshape(dp, mp)
    return MeshConfig(Mesh(grid, ("groups", "nodes")), dp, mp, source)


def get_mesh() -> "MeshConfig":
    """The process-wide mesh decision, resolved once from
    ``NOMAD_TPU_MESH`` (see ``_resolve_mesh``). Call ``reset_mesh()``
    after changing the env in tests."""
    global _mesh_config
    cfg = _mesh_config
    if cfg is not None:
        return cfg
    with _mesh_lock:
        if _mesh_config is None:
            _mesh_config = _resolve_mesh()
        return _mesh_config


def reset_mesh() -> None:
    global _mesh_config
    with _mesh_lock:
        _mesh_config = None
        _shard_drops.clear()


def shard_drops() -> dict[str, int]:
    """``"<axis>:<dim>%<mesh size>" -> count`` of the axes ``shard_put``
    replicated because the mesh did not divide them (e.g. a G=1 batch on
    a 2-wide groups axis, or a degenerate ``[G, 1]`` broadcast)."""
    with _mesh_lock:
        return dict(_shard_drops)


_transfer_tls = threading.local()  # .count, .bytes: this thread's hand-offs


def _note_transfer(x) -> None:
    _transfer_tls.count = getattr(_transfer_tls, "count", 0) + 1
    _transfer_tls.bytes = getattr(_transfer_tls, "bytes", 0) + int(
        getattr(x, "nbytes", 0)
    )


def transfer_totals() -> tuple[int, int]:
    """``(hand-offs, bytes)`` this thread has issued through the seam
    (``shard_put``, ``host_put``) so far: a span that wants to say how
    many it held reads the pair before and after."""
    return (
        getattr(_transfer_tls, "count", 0),
        getattr(_transfer_tls, "bytes", 0),
    )


def host_put(x):
    """One host buffer to the default device in one hand-off: the packed
    operands of a kernel call where no mesh is active (a packed buffer
    carries no PartitionSpec; under a mesh the operands go through
    ``shard_put`` one by one). The buffer must be the caller's to give
    away: on the CPU backend the device array may alias it."""
    import jax

    _note_transfer(x)
    return jax.device_put(x)


def shard_put(x, axes, cfg: "MeshConfig | None" = None):
    """Place ``x`` on the mesh with PartitionSpec(*axes); the sanctioned
    device_put seam. ``axes`` entries are "groups"/"nodes"/None, one per
    array dim (trailing Nones may be omitted). Degenerate mesh or an
    axis size that does not divide the corresponding dim -> plain
    jnp.asarray (full replication semantics, unchanged jaxpr). Every
    axis dropped that way under an active mesh is counted in
    ``shard_drops()`` — replication is a layout decision, not a secret."""
    import jax.numpy as jnp

    if cfg is None:
        cfg = get_mesh()
    _note_transfer(x)
    if not cfg.active:
        return jnp.asarray(x)
    shape = getattr(x, "shape", None)
    if shape is None:
        x = jnp.asarray(x)
        shape = x.shape
    sizes = {"groups": cfg.dp, "nodes": cfg.mp}
    use = []
    for i, ax in enumerate(axes):
        if ax is None or i >= len(shape):
            use.append(None)
        elif shape[i] % sizes[ax] != 0:
            use.append(None)
            key = f"{ax}:{shape[i]}%{sizes[ax]}"
            with _mesh_lock:
                _shard_drops[key] = _shard_drops.get(key, 0) + 1
        else:
            use.append(ax)
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(x, NamedSharding(cfg.mesh, PartitionSpec(*use)))


# -- incremental score-state seam ---------------------------------------------
#
# ``NOMAD_TPU_INCREMENTAL`` gates the DeviceStateCache's score-state
# persistence (device/cache.py): with it on, the per-pass ``used``
# tensor stays device-resident across passes and only dirty slices
# re-upload. Resolved once like the mesh spec; the gate is PYTHON-level
# (the resident buffer has the same aval as a fresh ``shard_put``), so
# flipping it can never change a traced program — the jaxlint differ
# (analysis/jaxlint/diff.py: prove_incremental_invariance) pins that.

_INCR_ENV = "NOMAD_TPU_INCREMENTAL"

_incr_lock = threading.Lock()
_incr_enabled = None  # cached bool | None (None = not resolved yet)


def incremental_enabled() -> bool:
    """The process-wide incremental-rescoring decision, resolved once
    from ``NOMAD_TPU_INCREMENTAL`` (``on``/``1``/``true`` enable; unset
    or anything else is off — the from-scratch reference path). Call
    ``reset_incremental()`` after changing the env in tests."""
    global _incr_enabled
    val = _incr_enabled
    if val is not None:
        return val
    with _incr_lock:
        if _incr_enabled is None:
            spec = os.environ.get(_INCR_ENV, "")
            _incr_enabled = spec.strip().lower() in ("on", "1", "true")
        return _incr_enabled


def reset_incremental() -> None:
    global _incr_enabled
    with _incr_lock:
        _incr_enabled = None


def transfer_fence(*arrays) -> None:
    """The ONE sanctioned ``jax.block_until_ready`` fence of the
    pipelined device loop. ``shard_put``/per-shard patch uploads
    dispatch asynchronously; the double-buffered score-state generations
    swap on commit, and THIS is where the swap synchronizes — never
    inside the upload path, or the overlap the pipeline exists to win
    is serialized away."""
    import jax

    for a in arrays:
        if a is not None:
            jax.block_until_ready(a)
