"""Shared launcher CLI surface.

Every driver that takes a model architecture, a workload set, or the
``--out``/``--seed`` conventions goes through these helpers instead of a
hand-rolled parser, so flags mean the same thing across
``launch/serve.py``, ``launch/realize.py`` and
``benchmarks/table1_dse.py``:

* ``--arch NAME`` + ``--reduced`` — a model config from
  ``repro.configs.get_config`` (``--reduced`` applies the CPU/CI-sized
  variant);
* ``--workload NAME=SPEC`` (repeatable) — workload graphs through the
  single ``repro.core.workloads.make_workload`` registry; a bare SPEC is
  allowed when the binding target has exactly one workload name.  Unknown
  specs raise ``make_workload``'s preset listing;
* ``--out PATH`` / ``--seed N`` — artifact path and base RNG seed;
* :func:`enable_compile_cache` — the persistent JAX compilation cache,
  turned on by each entry point's ``__main__`` (never at import).

Import-light on purpose: graph builders and model configs load inside
the resolver functions, not at module import (drivers pre-parse argv
before heavyweight imports).
"""

from __future__ import annotations

import argparse
import os
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

# the compile cache's home when JAX_COMPILATION_CACHE_DIR is unset: a fixed
# path in the checkout (git-ignored) — the directory is part of what a
# later run must find again, so it never moves
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing here names another directory; otherwise the cache lives at
    :data:`DEFAULT_COMPILE_CACHE`.  Every compile is kept, however short:
    a realized program is many sub-second stage compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(DEFAULT_COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir

# ---------------------------------------------------------------------------
# --arch / --reduced
# ---------------------------------------------------------------------------


def add_arch_args(ap: argparse.ArgumentParser, required: bool = True,
                  default: Optional[str] = None) -> None:
    ap.add_argument("--arch", required=required, default=default,
                    help="model config name (repro.configs.get_config)")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced-size config variant (CPU / CI runs)")


def model_config(args: argparse.Namespace):
    """Resolve ``--arch``/``--reduced`` into a ModelConfig."""
    from ..configs import get_config
    cfg = get_config(args.arch)
    return cfg.reduced() if args.reduced else cfg


# ---------------------------------------------------------------------------
# --workload NAME=SPEC
# ---------------------------------------------------------------------------


def add_workload_args(ap: argparse.ArgumentParser,
                      help_extra: str = "") -> None:
    ap.add_argument(
        "--workload", action="append", default=[], metavar="NAME=SPEC",
        help="workload graph binding (repeatable); SPEC is a registry "
             "preset (tf-quick, moe-quick, mla-quick, ...) or a "
             "parameterized spec ('transformer:k=v,...', 'moe:...', "
             "'mla:...', 'lm:<config>') — see "
             "repro.core.workloads.make_workload. " + help_extra)


def workload_bindings(items: Sequence[str],
                      names: Optional[Sequence[str]] = None
                      ) -> Dict[str, str]:
    """Parse ``NAME=SPEC`` items into ``{name: spec}``.

    With ``names`` given (e.g. the workload names a checkpoint was swept
    over), a bare ``SPEC`` binds to the single name — including
    parameterized specs like ``transformer:k=v`` whose first ``=`` is
    part of the spec, not a binding — and every name must end up bound:
    half-specified portfolios fail loudly instead of silently dropping
    workloads.
    """
    out: Dict[str, str] = {}
    for s in items:
        name, sep, spec = s.partition("=")
        if sep and ":" not in name and "," not in name:
            pass                        # NAME=SPEC binding
        elif names is not None and len(names) == 1:
            # bare SPEC — including parameterized ones whose first '='
            # sits inside the k=v tail ('transformer:n_layers=1,...')
            name, spec = names[0], s
        elif names is not None:
            raise SystemExit(
                f"--workload {s!r}: target has workloads {list(names)}; "
                f"bind explicitly with NAME=SPEC")
        else:
            name, spec = s, s           # standalone: spec doubles as name
        out[name] = spec
    if names is not None:
        missing = [n for n in names if n not in out]
        if missing:
            raise SystemExit(
                f"no --workload binding for workload(s) {missing}")
    return out


def resolve_workloads(bindings: Dict[str, str],
                      builder: Optional[Callable] = None) -> Dict:
    """``{name: spec}`` -> ``{name: Graph}`` via the workload registry.

    Unknown specs raise ``make_workload``'s error listing the registered
    presets (every driver keeps that contract).
    """
    if builder is None:
        from ..core.workloads import make_workload as builder
    return {name: builder(spec) for name, spec in bindings.items()}


# ---------------------------------------------------------------------------
# NAME=VALUE option lists (--weight, etc.)
# ---------------------------------------------------------------------------


def parse_kv(items: Optional[Sequence[str]], cast: Callable = str,
             flag: str = "option") -> Optional[Dict[str, object]]:
    """Parse repeated ``NAME=VALUE`` flags; None when nothing was given."""
    if not items:
        return None
    out: Dict[str, object] = {}
    for item in items:
        name, sep, val = item.partition("=")
        if not sep:
            raise SystemExit(f"{flag} {item!r} is not NAME=VALUE")
        try:
            out[name] = cast(val)
        except ValueError as e:
            raise SystemExit(f"{flag} {item!r}: {e}")
    return out


# ---------------------------------------------------------------------------
# --out / --seed
# ---------------------------------------------------------------------------


def add_out_arg(ap: argparse.ArgumentParser, default: Optional[str] = None,
                what: str = "result artifact") -> None:
    ap.add_argument("--out", default=default,
                    help=f"write the {what} here"
                         + (f" (default {default})" if default else ""))


def add_seed_arg(ap: argparse.ArgumentParser, default: int = 0) -> None:
    ap.add_argument("--seed", type=int, default=default,
                    help=f"base RNG seed (default {default})")
