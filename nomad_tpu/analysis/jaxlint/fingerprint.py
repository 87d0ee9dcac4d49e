"""JXL006 — canonical jaxpr fingerprints.

A fingerprint is a sha256 over a canonical rendering of a ClosedJaxpr:
variables renamed in first-use order, equations serialized as
(primitive, sorted normalized params, input slots, output avals),
sub-jaxprs (scan/while/cond/pjit bodies) recursed with independent
numbering, consts reduced to (shape, dtype, content hash). Two traces
of the same program — in different processes, under different ambient
mesh/explain/config state — produce the same fingerprint; any change to
the traced computation changes it. This is what turns "identical jaxpr,
zero added retraces" from scattered per-test assertions into a
whole-fleet invariant the differ (jaxlint.diff) can prove.

The renderer must be process-stable: no ``id()``, no raw ``repr`` of
objects whose repr embeds addresses (those are scrubbed), no dict/set
iteration-order dependence (params are sorted by key).
"""

from __future__ import annotations

import hashlib
import re
import threading

_ADDR_RE = re.compile(r"0x[0-9a-fA-F]+")


def _aval_str(aval) -> str:
    weak = ",w" if getattr(aval, "weak_type", False) else ""
    shape = ",".join(str(int(d)) for d in getattr(aval, "shape", ()))
    return f"{getattr(aval, 'dtype', '?')}[{shape}]{weak}"


def _norm_param(v) -> str:
    """Normalize one equation param to a process-stable string."""
    import numpy as np

    if hasattr(v, "jaxpr") or hasattr(v, "eqns"):  # ClosedJaxpr / Jaxpr
        closed = v if hasattr(v, "jaxpr") else None
        if closed is not None:
            return "jaxpr{" + canonical_text(closed) + "}"
        return "jaxpr{" + _canon_open(v) + "}"
    if isinstance(v, (tuple, list)):
        return "[" + ",".join(_norm_param(x) for x in v) + "]"
    if isinstance(v, dict):
        return "{" + ",".join(
            f"{k}:{_norm_param(x)}" for k, x in sorted(v.items())
        ) + "}"
    if isinstance(v, np.dtype):
        return str(v)
    if hasattr(v, "shape") and hasattr(v, "dtype") and hasattr(
        v, "__array__"
    ):
        arr = np.ascontiguousarray(np.asarray(v))
        digest = hashlib.sha256(arr.tobytes()).hexdigest()[:12]
        return f"arr({_ADDR_RE.sub('', str(arr.dtype))}" \
               f"[{','.join(map(str, arr.shape))}],{digest})"
    if callable(v):
        return f"fn:{getattr(v, '__name__', type(v).__name__)}"
    return _ADDR_RE.sub("0xADDR", repr(v))


def _var_namer():
    names: dict = {}

    def name_of(v):
        import jax.extend

        if isinstance(v, jax.extend.core.Literal):
            return f"lit({_norm_param(v.val)}:{_aval_str(v.aval)})"
        if v not in names:
            names[v] = f"v{len(names)}"
        return names[v]

    return name_of


def _canon_open(jaxpr) -> str:
    """Canonical text of an OPEN jaxpr (no consts attached)."""
    name_of = _var_namer()
    lines = []
    lines.append(
        "in=" + ",".join(f"{name_of(v)}:{_aval_str(v.aval)}"
                         for v in jaxpr.invars)
    )
    if jaxpr.constvars:
        lines.append(
            "constvars=" + ",".join(
                f"{name_of(v)}:{_aval_str(v.aval)}"
                for v in jaxpr.constvars
            )
        )
    for eqn in jaxpr.eqns:
        params = ",".join(
            f"{k}={_norm_param(v)}" for k, v in sorted(eqn.params.items())
        )
        ins = ",".join(name_of(v) for v in eqn.invars)
        outs = ",".join(
            f"{name_of(v)}:{_aval_str(v.aval)}" for v in eqn.outvars
        )
        lines.append(f"{eqn.primitive.name}({ins})->({outs})|{params}")
    lines.append("out=" + ",".join(name_of(v) for v in jaxpr.outvars))
    return "\n".join(lines)


def canonical_text(closed) -> str:
    """Canonical rendering of a ClosedJaxpr, consts included by value."""
    consts = ",".join(_norm_param(c) for c in closed.consts)
    body = _canon_open(closed.jaxpr)
    return (f"consts=[{consts}]\n" if consts else "") + body


def fingerprint(closed) -> str:
    """16-hex-char canonical hash of a ClosedJaxpr."""
    return hashlib.sha256(
        canonical_text(closed).encode("utf-8")
    ).hexdigest()[:16]


# -- per-kernel fingerprint cache --------------------------------------------
#
# (kernel name, spec sig) -> fingerprint. Re-tracing is cheap (~ms at
# production shapes, no compile) but not free; the bench detail blocks
# and /v1/agent/trace read through this cache so repeated surfacing
# costs one dict lookup.

_fp_lock = threading.Lock()
_fp_cache: dict[tuple[str, str], str] = {}


def fingerprint_for(entry, sig: str) -> str:
    """Fingerprint of one recorded config of one kernel (cached)."""
    key = (entry.name, sig)
    with _fp_lock:
        cached = _fp_cache.get(key)
    if cached is not None:
        return cached
    from . import retracer

    fp = fingerprint(retracer.retrace(entry, entry.specs[sig]))
    with _fp_lock:
        _fp_cache[key] = fp
    return fp


def reset_fingerprint_cache() -> None:
    with _fp_lock:
        _fp_cache.clear()


def fingerprint_table(registry=None, production_only: bool = True) -> dict:
    """{kernel name: {config label: fingerprint}} for every registered
    kernel with at least one recorded spec. The bench ``detail`` blocks
    and the /v1/agent/trace kernel profiles embed this so cross-run
    jaxpr drift is diffable from recorded artifacts."""
    from ...utils import backend
    from . import retracer

    if registry is None:
        registry = backend.kernel_registry()
    reg = (
        retracer.production_kernels(registry)
        if production_only
        else registry
    )
    out: dict = {}
    for name, entry in sorted(reg.items()):
        configs = {}
        for sig in entry.specs:
            configs[retracer.spec_label(entry, sig)] = fingerprint_for(
                entry, sig
            )
        if configs:
            out[entry.short] = configs
    return out
