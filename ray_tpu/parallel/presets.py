"""Parallelism presets: mesh + partition specs bound at ONE site.

The lift the elastic-training loop needs (SNIPPETS.md [2]'s unified
jit+shard_map decorator, generalized): a step function decorated with
``sharded_jit(in_specs=..., out_specs=...)`` names only its partition
specs; the mesh it runs on is resolved at CALL time from a process-wide
default binding. A gang resize then re-meshes every decorated function
with one ``rebind_default_mesh()`` (or simply by re-running
``session.get_mesh()`` in the respawned worker) instead of re-wiring
each call site — sharding config lives at one site.

Three layers:

* **default-mesh registry** — ``set_default_mesh`` / ``default_mesh`` /
  ``rebind_default_mesh``: the process binding ``sharded_jit`` resolves
  against. ``ray_tpu.train.session.get_mesh()`` installs it per worker.
* **ParallelPreset** — a named (MeshSpec, ShardingRules) pair; ``bind()``
  builds the mesh over the current devices and installs the binding.
* **sharded_jit** — the unified decorator: with in/out specs it wraps the
  function in ``jax.shard_map`` over the resolved mesh then ``jax.jit``;
  without specs it is a late-mesh ``jax.jit``. Compilations are cached
  per mesh binding, so steady-state calls pay one dict probe.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from functools import wraps
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.parallel.sharding import ShardingRules

# --------------------------------------------------------------------------
# process-default mesh binding
# --------------------------------------------------------------------------

_lock = threading.Lock()
_binding: Dict[str, Any] = {"mesh": None, "rules": None, "spec": None,
                            "generation": 0}


def set_default_mesh(mesh, rules: Optional[ShardingRules] = None,
                     spec: Optional[MeshSpec] = None) -> None:
    """Install `mesh` as the process default that ``sharded_jit`` (and
    ``default_rules``) resolve at call time. Re-installing bumps the
    binding generation, invalidating every decorated function's cached
    compilation."""
    with _lock:
        _binding["mesh"] = mesh
        if rules is not None:
            _binding["rules"] = rules
        if spec is not None:
            _binding["spec"] = spec
        _binding["generation"] += 1


def default_mesh():
    """The current process-default mesh (None if never bound)."""
    with _lock:
        return _binding["mesh"]


def default_rules() -> Optional[ShardingRules]:
    with _lock:
        return _binding["rules"]


def rebind_default_mesh(spec: Optional[MeshSpec] = None,
                        devices: Optional[Sequence] = None,
                        rules: Optional[ShardingRules] = None):
    """Rebuild the default mesh — the one-call re-mesh an elastic
    rebuild performs after a gang resize. Uses `spec` (or the spec the
    binding was installed with, or dp=-1) over `devices` (default: the
    runtime's CURRENT device set, which a resize just changed). Every
    ``sharded_jit`` function recompiles against the new mesh on its
    next call."""
    with _lock:
        spec = spec or _binding["spec"] or MeshSpec(dp=-1)
    mesh = build_mesh(spec, devices)
    set_default_mesh(mesh, rules=rules, spec=spec)
    return mesh


def _binding_snapshot() -> Tuple[int, Any]:
    with _lock:
        return _binding["generation"], _binding["mesh"]


# --------------------------------------------------------------------------
# named presets
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ParallelPreset:
    """A named parallelism recipe: mesh shape + sharding rules, bound in
    one call. ``bind()`` is what a worker (or an elastic rebuild) runs;
    everything downstream resolves through the default-mesh registry."""

    name: str
    mesh_spec: MeshSpec
    rules_name: str = "fsdp"

    def rules(self) -> ShardingRules:
        return getattr(ShardingRules, self.rules_name)()

    def build(self, devices: Optional[Sequence] = None):
        return build_mesh(self.mesh_spec, devices)

    def bind(self, devices: Optional[Sequence] = None):
        """Build over the current (or given) devices and install as the
        process default; returns the mesh."""
        mesh = self.build(devices)
        set_default_mesh(mesh, rules=self.rules(), spec=self.mesh_spec)
        return mesh


PRESETS: Dict[str, ParallelPreset] = {
    "dp": ParallelPreset("dp", MeshSpec(dp=-1), "dp"),
    "fsdp": ParallelPreset("fsdp", MeshSpec(fsdp=-1), "fsdp"),
    "fsdp_tp": ParallelPreset("fsdp_tp", MeshSpec(fsdp=-1, tp=1), "fsdp_tp"),
    "full": ParallelPreset("full", MeshSpec(fsdp=-1, tp=1), "full"),
    "ep": ParallelPreset("ep", MeshSpec(dp=-1, fsdp=1), "ep"),
}


def get_preset(name: str) -> ParallelPreset:
    try:
        return PRESETS[name]
    except KeyError:
        raise ValueError(
            f"unknown parallel preset {name!r}; have {sorted(PRESETS)}")


# --------------------------------------------------------------------------
# the unified jit + shard_map decorator
# --------------------------------------------------------------------------

def sharded_jit(fn: Optional[Callable] = None, *,
                in_specs: Any = None,
                out_specs: Any = None,
                mesh=None,
                axis_names: Optional[Sequence[str]] = None,
                static_argnums: Any = None,
                donate_argnums: Any = None) -> Callable:
    """Unified jit+shard_map decorator with late mesh binding.

    in_specs/out_specs: PartitionSpecs (or pytrees of them) for the
        wrapped function's args/results; both given => the body runs
        under ``jax.shard_map`` on the resolved mesh. Neither => plain
        ``jax.jit`` (the mesh still gates recompilation, so sharded
        closures rebuild after a rebind too).
    mesh: a fixed mesh, or None to resolve the process default at every
        CALL — the elastic contract: decorate once, rebind per resize.
    axis_names: the manual axes for shard_map (default: all mesh axes).
    static_argnums/donate_argnums: forwarded to ``jax.jit``.
    """
    if (in_specs is None) != (out_specs is None):
        raise ValueError("sharded_jit needs both in_specs and out_specs "
                         "(or neither, for a late-mesh plain jit)")

    def deco(f: Callable) -> Callable:
        cache: Dict[Any, Callable] = {}

        @wraps(f)
        def wrapped(*args, **kwargs):
            import jax

            if mesh is not None:
                key, m = ("fixed", id(mesh)), mesh
            else:
                gen, m = _binding_snapshot()
                if m is None:
                    raise RuntimeError(
                        "sharded_jit: no default mesh bound — call "
                        "ray_tpu.parallel.presets.set_default_mesh / "
                        "a preset's bind() / session.get_mesh() first, "
                        "or pass mesh= explicitly")
                key = ("default", gen)
            g = cache.get(key)
            if g is None:
                body = f
                if in_specs is not None:
                    names = frozenset(axis_names if axis_names is not None
                                      else m.axis_names)
                    body = jax.shard_map(f, mesh=m, in_specs=in_specs,
                                         out_specs=out_specs,
                                         axis_names=names)
                jit_kw: Dict[str, Any] = {}
                if static_argnums is not None:
                    jit_kw["static_argnums"] = static_argnums
                if donate_argnums is not None:
                    jit_kw["donate_argnums"] = donate_argnums
                g = jax.jit(body, **jit_kw)
                # one live binding per function: a rebind obsoletes the
                # old mesh's executable (its devices may be gone)
                cache.clear()
                cache[key] = g
            return g(*args, **kwargs)

        wrapped.cache_info = lambda: dict(entries=len(cache))  # type: ignore
        return wrapped

    return deco(fn) if fn is not None else deco
