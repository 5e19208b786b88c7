"""Attention-backend registry: declare capabilities, dispatch one entry.

Each backend registers a ``run`` callable plus a ``supports(call)``
capability predicate and a priority; :func:`attention` is the single
dispatch entry the model layer calls. Selection is explicit and
testable:

* ``AttnSpec(backend="auto")`` picks the highest-ranked backend whose
  ``supports(call)`` is True. Pallas backends out-rank XLA only on TPU
  (off-TPU they would run in interpret mode — still selectable
  explicitly, never picked automatically); the ``reference`` oracle
  ranks last, so the fallback chain is pallas -> xla -> reference.
* An exact name (``"pallas_hdp_block"``) or family tag (``"pallas"``)
  requests that implementation; if it cannot serve the call the spec
  either falls down the auto chain (``allow_fallback=True``, the
  default — e.g. the FUM kernel cannot express sliding windows) or
  raises ``BackendUnsupported``.
* ``REPRO_ATTN_BACKEND`` (env) overrides the DEFAULT spec only — calls
  that thread an explicit spec are unaffected. CI uses it to keep the
  oracle path exercised on every PR.

Registering a new backend is one ``@register_backend`` function plus one
row in the conformance matrix (tests/test_attention_registry.py) — the
extension point for the ROADMAP's TPU-native decode work.
"""
from __future__ import annotations

import dataclasses
import importlib
import os
from typing import Callable, Dict, List, Optional

import jax

from repro.attention.spec import AttnCall, AttnSpec

#: env var forcing the *default* spec's backend (explicit specs win).
BACKEND_ENV = "REPRO_ATTN_BACKEND"

#: env var deciding how policy="auto" specs rank auto-selected backends:
#: "cost" routes through the repro.autotune cost model; anything else
#: (including unset) keeps the static priority order.
POLICY_ENV = "REPRO_ATTN_POLICY"

_BACKEND_MODULES = ("repro.attention.reference", "repro.attention.backends")


class BackendUnsupported(ValueError):
    """Requested backend cannot serve the call and fallback is disabled."""


@dataclasses.dataclass(frozen=True)
class Backend:
    """A registered attention implementation.

    ``run(q, k, v, call, *, q_pos, k_pos, cache, page_table)`` returns
    ``(out, AttnStats | None)``. ``priority`` ranks auto-selection
    off-TPU, ``tpu_priority`` on TPU (Pallas backends invert the order).
    """

    name: str
    run: Callable
    supports: Callable[[AttnCall], bool]
    priority: int
    tpu_priority: int
    tags: frozenset

    def rank(self, call: AttnCall) -> int:
        del call  # ranking is platform-, not call-, dependent today
        return self.tpu_priority if _on_tpu() else self.priority


_REGISTRY: Dict[str, Backend] = {}
_LOADED = False


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def register_backend(name: str, *, supports: Callable[[AttnCall], bool],
                     priority: int, tpu_priority: Optional[int] = None,
                     tags=()):
    """Decorator registering ``fn`` as backend ``name``."""

    def deco(fn: Callable) -> Callable:
        if name in _REGISTRY:
            raise ValueError(f"backend {name!r} already registered")
        _REGISTRY[name] = Backend(
            name=name, run=fn, supports=supports, priority=priority,
            tpu_priority=priority if tpu_priority is None else tpu_priority,
            tags=frozenset(tags))
        return fn

    return deco


def _ensure_backends() -> None:
    """Import the backend modules lazily (they import the model layer,
    which imports this package — top-level imports would cycle)."""
    global _LOADED
    if not _LOADED:
        _LOADED = True
        for mod in _BACKEND_MODULES:
            importlib.import_module(mod)


def list_backends() -> List[Backend]:
    _ensure_backends()
    return sorted(_REGISTRY.values(), key=lambda b: (-b.priority, b.name))


def get_backend(name: str) -> Backend:
    _ensure_backends()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown attention backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def known_backend_names() -> List[str]:
    """Every resolvable request: backend names, family tags, "auto"."""
    _ensure_backends()
    names = {n for b in _REGISTRY.values() for n in (b.name, *b.tags)}
    return sorted(names | {"auto"})


def default_spec() -> AttnSpec:
    """The spec used when none is threaded (honors REPRO_ATTN_BACKEND)."""
    return AttnSpec(backend=os.environ.get(BACKEND_ENV, "auto"))


def effective_policy(spec: AttnSpec) -> str:
    """The selection policy ``spec`` actually runs under: its own unless
    "auto", in which case REPRO_ATTN_POLICY=cost opts the process in."""
    if spec.policy != "auto":
        return spec.policy
    return ("cost" if os.environ.get(POLICY_ENV, "").strip() == "cost"
            else "static")


def resolve_backend(call: AttnCall, spec: Optional[AttnSpec] = None, *,
                    sig=None, tuner=None) -> Backend:
    """Pick the backend serving ``call`` under ``spec`` (static logic).

    ``sig`` (a :class:`repro.autotune.cost.CallSig`) activates cost-based
    ranking of the auto candidates when the spec's effective policy is
    "cost"; without it (or under explicit requests) the static priority
    order decides. ``tuner`` overrides the process-default tuner.
    """
    _ensure_backends()
    spec = spec if spec is not None else default_spec()
    cands = [b for b in _REGISTRY.values() if b.supports(call)]
    if not cands:
        raise BackendUnsupported(f"no registered backend supports {call}")

    def best(pool):
        return max(pool, key=lambda b: (b.rank(call), b.name))

    req = spec.requested_for(call.mode)
    if req == "auto":
        # "auto" always consults the env override, so REPRO_ATTN_BACKEND
        # forces the oracle end-to-end even through explicit specs that
        # only pin the layout; explicit non-auto requests still win
        req = os.environ.get(BACKEND_ENV, "auto")
    if req == "auto" and sig is not None and effective_policy(spec) == "cost":
        if tuner is None:
            from repro.autotune.tuner import default_tuner
            tuner = default_tuner()
        return tuner.choose(call, sig, cands)
    if req != "auto":
        known = {n for b in _REGISTRY.values() for n in (b.name, *b.tags)}
        if req not in known:
            raise KeyError(
                f"unknown attention backend {req!r}; registered: "
                f"{sorted(known)}")
        exact = _REGISTRY.get(req)
        if exact is not None and exact in cands:
            return exact
        tagged = [b for b in cands if req in b.tags]
        if tagged:
            return best(tagged)
        if not spec.allow_fallback:
            raise BackendUnsupported(
                f"backend {req!r} does not support {call} "
                "(allow_fallback=False)")
    return best(cands)


def attention(q, k, v, call: AttnCall, *, spec: Optional[AttnSpec] = None,
              q_pos=None, k_pos=None, cache=None, page_table=None):
    """Single dispatch entry: resolve a backend and run the call.

    q [B,N,G,Sq,hd]; k/v [B,Sk,N,hd] (dense layout; None for paged calls,
    whose K/V live in ``cache`` pools indexed by ``page_table``).
    ``q_pos``/``k_pos`` are broadcastable position arrays (-1 = invalid);
    they default to ``arange`` when omitted. Returns
    ``(out [B,N,G,Sq,hd], AttnStats | None)``.
    """
    import jax.numpy as jnp

    if q_pos is None:
        q_pos = jnp.arange(q.shape[-2])
    if k_pos is None and k is not None:
        k_pos = jnp.arange(k.shape[1])
    sig = None
    eff_spec = spec if spec is not None else default_spec()
    if (effective_policy(eff_spec) == "cost"
            and eff_spec.requested_for(call.mode) == "auto"):
        # shapes/dtypes are static under tracing, so the signature (and
        # hence the choice) is burnt into the compiled program
        from repro.autotune.cost import call_signature
        from repro.distribution.tp import active_tp

        # under tensor-parallel serving this runs inside shard_map, so
        # q/cache shapes are already per-shard (local heads); the tp
        # degree keys the signature so cached probe results never cross
        # mesh shapes, and funds the collective term in the cost model
        sig = call_signature(call, q, k=k, cache=cache,
                             page_table=page_table, tp=active_tp())
    backend = resolve_backend(call, eff_spec, sig=sig)
    return backend.run(q, k, v, call, q_pos=q_pos, k_pos=k_pos,
                       cache=cache, page_table=page_table)
