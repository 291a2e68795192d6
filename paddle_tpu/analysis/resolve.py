"""Lightweight import/alias resolution for project-aware passes.

The SPMD surface is imported under many spellings — ``from jax.sharding
import PartitionSpec as P``, ``from jax import shard_map``, ``import
jax`` + ``jax.lax.psum`` — and passes that key on those symbols must see
through every one of them.  :class:`Imports` builds a per-file table mapping
local names to canonical dotted paths (resolving relative imports against
the file's dotted module name when known), and :func:`Imports.canonical`
rewrites any ``Name``/``Attribute`` chain through it.

On top of that sit the symbol classifiers the ``sharding-spec-coverage``
pass uses: :func:`is_shard_map`, :func:`is_partition_spec`,
:func:`collective_axis_arg`, and :func:`mesh_axis_names`.  They match by
canonical-path suffix so both the jax spellings and this repo's wrappers
(``distributed/collective.mesh_*``) resolve
to the same semantic symbol.
"""
from __future__ import annotations

import ast


class Imports:
    """Local name -> canonical dotted path for one parsed module."""

    def __init__(self, tree: ast.AST, module: str | None = None):
        self.module = module            # dotted name of the analyzed file
        self.aliases: dict[str, str] = {}
        self.star_modules: list[str] = []
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.asname:
                        self.aliases[a.asname] = a.name
                    else:               # `import a.b.c` binds only `a`
                        root = a.name.split(".")[0]
                        self.aliases[root] = root
            elif isinstance(node, ast.ImportFrom):
                base = self._from_base(node)
                for a in node.names:
                    if a.name == "*":
                        self.star_modules.append(base)
                        continue
                    target = f"{base}.{a.name}" if base else a.name
                    self.aliases[a.asname or a.name] = target

    def _from_base(self, node: ast.ImportFrom) -> str:
        mod = node.module or ""
        if not node.level:
            return mod
        if self.module:
            parts = self.module.split(".")[:-node.level]
            return ".".join(parts + mod.split(".")) if mod \
                else ".".join(parts)
        return mod                      # relative, module unknown: keep tail

    def canonical(self, node) -> str | None:
        """Canonical dotted path of a ``Name``/``Attribute`` chain, with the
        root name rewritten through the import table; None for anything
        else (calls, subscripts, ...)."""
        attrs = []
        while isinstance(node, ast.Attribute):
            attrs.append(node.attr)
            node = node.value
        if not isinstance(node, ast.Name):
            return None
        root = self.aliases.get(node.id, node.id)
        return ".".join([root] + list(reversed(attrs)))


def _match(canon: str | None, suffixes) -> bool:
    if not canon:
        return False
    return any(canon == s or canon.endswith("." + s) for s in suffixes)


# every spelling that means jax's shard_map
_SHARD_MAP = ("jax.shard_map", "jax.experimental.shard_map.shard_map",
              "shard_map")
_PARTITION_SPEC = ("jax.sharding.PartitionSpec",
                   "jax.experimental.pjit.PartitionSpec", "PartitionSpec")
_NAMED_SHARDING = ("jax.sharding.NamedSharding", "NamedSharding")
# jit entry points that accept in_shardings=/out_shardings= keywords
_JIT = ("jax.jit", "pjit")
# canonical-path suffix -> positional index of the axis-name argument
_COLLECTIVES = {
    "lax.psum": 1, "lax.pmean": 1, "lax.pmax": 1, "lax.pmin": 1,
    "lax.ppermute": 1, "lax.pshuffle": 1, "lax.all_gather": 1,
    "lax.all_to_all": 1, "lax.psum_scatter": 1, "lax.axis_index": 0,
    "collective.mesh_all_reduce": 1, "collective.mesh_all_gather": 1,
    "collective.mesh_reduce_scatter": 1, "collective.mesh_all_to_all": 1,
    "collective.mesh_ppermute": 1,
}
# mesh constructors -> positional index of the axis-names argument
_MESH_CTORS = {"jax.sharding.Mesh": 1, "jax.make_mesh": 1, "Mesh": 1}


def is_shard_map(canon: str | None) -> bool:
    return _match(canon, _SHARD_MAP)


def is_partition_spec(canon: str | None) -> bool:
    return _match(canon, _PARTITION_SPEC)


def is_named_sharding(canon: str | None) -> bool:
    return _match(canon, _NAMED_SHARDING)


def is_jit(canon: str | None) -> bool:
    return _match(canon, _JIT)


def collective_axis_arg(canon: str | None):
    """Positional index of the collective's axis-name argument, or None if
    ``canon`` is not a recognized collective."""
    if not canon:
        return None
    for suffix, idx in _COLLECTIVES.items():
        if canon == suffix or canon.endswith("." + suffix):
            return idx
    return None


def _literal_axis_names(node) -> list[str] | None:
    """Axis names from a literal str / tuple / list of strs, else None."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        names = []
        for e in node.elts:
            if not (isinstance(e, ast.Constant) and isinstance(e.value, str)):
                return None
            names.append(e.value)
        return names
    return None


def mesh_axis_names(call: ast.Call, imports: Imports) -> list[str] | None:
    """Axis names of a mesh-constructor call when they are literal —
    ``Mesh(devices, ("dp", "mp"))`` / ``jax.make_mesh((2, 2), ("dp", "mp"))``
    — else None."""
    canon = imports.canonical(call.func)
    for suffix, idx in _MESH_CTORS.items():
        if canon == suffix or (canon and canon.endswith("." + suffix)):
            node = call.args[idx] if len(call.args) > idx else None
            if node is None:
                for kw in call.keywords:
                    if kw.arg == "axis_names":
                        node = kw.value
            return _literal_axis_names(node) if node is not None else None
    return None
