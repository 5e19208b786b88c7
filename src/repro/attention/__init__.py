"""Unified attention-backend registry (one dispatch layer, seven backends).

Public API:

* :class:`AttnCall` — frozen descriptor of one attention invocation.
* :class:`AttnSpec` — backend-selection request (name, family tag or
  "auto", per-mode overrides, cache layout).
* :func:`attention` — the single dispatch entry:
  ``attention(q, k, v, call, *, spec, q_pos, k_pos, cache, page_table)``
  returns ``(out, AttnStats | None)``.
* :func:`register_backend` / :func:`resolve_backend` /
  :func:`list_backends` — the registry itself.

Backends (see ``backends.py`` / ``reference.py``): ``reference`` (the
materializing oracle), ``xla_dense``, ``xla_hdp``, ``paged_hdp_decode``,
``pallas_flash``, ``pallas_hdp_block``, ``pallas_paged_decode``.
Auto-selection is one fixed rule: the highest-ranked backend whose
``supports(call)`` holds, falling pallas -> xla -> reference (Pallas only
out-ranks XLA on TPU; off-TPU it runs in interpret mode when explicitly
requested).
"""
from repro.attention.registry import (BACKEND_ENV, Backend,
                                      BackendUnsupported, attention,
                                      default_spec, get_backend,
                                      known_backend_names, list_backends,
                                      register_backend, resolve_backend)
from repro.attention.spec import AttnCall, AttnSpec, DraftProfile
from repro.attention.stats import AttnStats, normalize_stats

__all__ = [
    "AttnCall", "AttnSpec", "AttnStats", "Backend", "BackendUnsupported",
    "BACKEND_ENV", "DraftProfile", "attention", "default_spec",
    "get_backend", "known_backend_names", "list_backends",
    "normalize_stats", "register_backend", "resolve_backend",
]
