"""Unified mesh partitioner: ONE sharding spec from program to pjit.

The reference unified tensor/pipeline/data parallelism under a single
execution stack (CompiledProgram + ParallelExecutor + the multi-device
graph passes); here the same unification is a ``ShardingSpec`` —
program-level sharding annotations over the canonical named axes of
``parallel/mesh.py`` (data/model/pipe/seq/expert/dcn_data) that every
layer consumes:

- ``Executor.prepare``/``run`` (static path): a
  ``CompiledProgram.with_mesh_sharding(spec)`` program places its
  persistable state per ``param_spec``, shards feed batches per
  ``feed_spec``, and pins the spec'd names inside each compiled device
  segment with ``with_sharding_constraint`` — the pjit lowering.
- the functional trainers (pipeline/data_parallel/models): pytrees map
  through the same spec by tree path (``tree_specs``/``tree_shardings``).
- the checkpoint layer: ``checkpoint_axes`` derives ``save(axes=)``
  annotations for PR 6's reshard planner from the very same spec.

Specs are name-keyed. ``params`` holds exact names; ``rules`` holds
``(fnmatch pattern, PartitionSpec)`` pairs tried in order — the
program-level analog of the reference's per-param attribute
annotations. A name matching neither is replicated. Feed arrays
default to batch-dim sharding over the mesh's data axes
(``dcn_data``+``data`` when hybrid), the hierarchical-allreduce
placement of mesh.py.
"""

import fnmatch

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.core.enforce import EnforceNotMet
from paddle_tpu.parallel.mesh import data_axes, get_mesh

__all__ = ["ShardingSpec"]


def _as_pspec(entry):
    if isinstance(entry, P):
        return entry
    if entry is None:
        return P()
    if isinstance(entry, (tuple, list)):
        return P(*entry)
    if isinstance(entry, str):
        return P(entry)
    raise EnforceNotMet(
        f"sharding entry must be a PartitionSpec / axis name / tuple / "
        f"None, got {type(entry).__name__}")


def _entry_axes(entry):
    """The mesh axis names one PartitionSpec DIMENSION entry references."""
    if entry is None:
        return ()
    if isinstance(entry, (tuple, list)):
        return tuple(entry)
    return (entry,)


def _leaf_path(path):
    """jax key-path -> "a/b/0" string the rules match against."""
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:  # pragma: no cover - exotic key types
            parts.append(str(k))
    return "/".join(parts)


class ShardingSpec:
    """Program-level sharding annotations over a named-axis mesh.

    ``params``: {exact name: PartitionSpec} — per-param placement.
    ``rules``: [(fnmatch pattern, PartitionSpec)] tried in order, after
    exact names; patterns match static var names ("w_qkv_3") and
    functional tree paths ("stages/w"). Unmatched names are replicated.
    ``feeds``: {feed name: PartitionSpec} overriding the default
    batch-dim-0 sharding over ``feed_batch_axes`` (default: the mesh's
    data axes, DCN-outermost — scalars stay replicated).
    """

    def __init__(self, mesh=None, params=None, rules=None, feeds=None,
                 feed_batch_axes=None):
        self.mesh = mesh if mesh is not None else get_mesh()
        self.params = {n: _as_pspec(s) for n, s in (params or {}).items()}
        self.rules = [(pat, _as_pspec(s)) for pat, s in (rules or [])]
        self.feeds = {n: _as_pspec(s) for n, s in (feeds or {}).items()}
        if feed_batch_axes is None:
            self.feed_batch_axes = data_axes(self.mesh)
        else:
            self.feed_batch_axes = tuple(feed_batch_axes)
        shape = dict(self.mesh.shape)
        for axes_src in ([("feed_batch_axes", P(self.feed_batch_axes))]
                         + [(f"params[{n!r}]", s)
                            for n, s in self.params.items()]
                         + [(f"rules[{pat!r}]", s)
                            for pat, s in self.rules]
                         + [(f"feeds[{n!r}]", s)
                            for n, s in self.feeds.items()]):
            where, sp = axes_src
            seen = []
            for entry in sp:
                for a in _entry_axes(entry):
                    if a not in shape:
                        raise EnforceNotMet(
                            f"ShardingSpec {where} references mesh axis "
                            f"{a!r}, but the mesh only has axes "
                            f"{tuple(shape)}")
                    if a in seen:
                        raise EnforceNotMet(
                            f"ShardingSpec {where} uses mesh axis {a!r} "
                            f"on more than one dimension")
                    seen.append(a)

    @classmethod
    def from_tree(cls, mesh, spec_tree, **kw):
        """Build a ShardingSpec from an existing PartitionSpec PYTREE
        (the currency of the functional models, e.g.
        ``models.transformer.param_specs``): every leaf becomes an
        exact path-keyed entry, so ``tree_specs`` round-trips it and
        ``checkpoint_axes``/executor interop come for free."""
        flat, _ = jax.tree_util.tree_flatten_with_path(
            spec_tree, is_leaf=lambda s: isinstance(s, P))
        return cls(mesh,
                   params={_leaf_path(p): s for p, s in flat}, **kw)

    # -- lookups -----------------------------------------------------------
    def _lookup(self, name):
        """Explicit entry for ``name`` (exact, then first matching
        rule), or None when the spec says nothing about it."""
        sp = self.params.get(name)
        if sp is not None:
            return sp
        for pat, sp in self.rules:
            if fnmatch.fnmatchcase(name, pat):
                return sp
        return None

    def param_spec(self, name):
        """PartitionSpec for a param/state name (replicated default)."""
        sp = self._lookup(name)
        return sp if sp is not None else P()

    def feed_spec(self, name, ndim):
        """PartitionSpec for a feed: explicit entry, else batch dim 0
        over the data axes (scalars replicated)."""
        sp = self.feeds.get(name)
        if sp is not None:
            return sp
        if ndim == 0 or not self.feed_batch_axes:
            return P()
        axes = (self.feed_batch_axes[0]
                if len(self.feed_batch_axes) == 1
                else tuple(self.feed_batch_axes))
        return P(axes)

    def axis_extent(self, entry):
        """Product of mesh extents one dimension entry shards over."""
        shape = dict(self.mesh.shape)
        n = 1
        for a in _entry_axes(entry):
            n *= shape[a]
        return n

    # -- shardings ---------------------------------------------------------
    def param_sharding(self, name):
        return NamedSharding(self.mesh, self.param_spec(name))

    def feed_sharding(self, name, ndim):
        return NamedSharding(self.mesh, self.feed_spec(name, ndim))

    def state_shardings(self, names):
        """{name: NamedSharding} for the executor's persistable state."""
        return {n: self.param_sharding(n) for n in names}

    def constraint_for(self, name):
        """NamedSharding to pin ``name`` to inside a compiled segment,
        or None when the spec has nothing explicit for it (replicated-
        by-default names are left to the partitioner). Gradient names
        (``<param>@GRAD``) inherit their param's placement — the
        gradient collective then reduces shard-local buffers instead of
        gathered replicas."""
        base = name[:-len("@GRAD")] if name.endswith("@GRAD") else name
        sp = self._lookup(base)
        return None if sp is None else NamedSharding(self.mesh, sp)

    def validate_leaf(self, name, shape, sp=None):
        """Divisibility check: every sharded dim of ``shape`` must
        divide by the extent of the axes tiling it."""
        sp = self.param_spec(name) if sp is None else sp
        for d, entry in enumerate(sp):
            if entry is None:
                continue
            if d >= len(shape):
                raise EnforceNotMet(
                    f"ShardingSpec for {name!r} shards dim {d} but the "
                    f"value has shape {tuple(shape)}")
            n = self.axis_extent(entry)
            if n > 1 and shape[d] % n != 0:
                raise EnforceNotMet(
                    f"ShardingSpec for {name!r}: dim {d} of shape "
                    f"{tuple(shape)} is not divisible by the "
                    f"{n}-way {_entry_axes(entry)} tiling")
        return sp

    # -- placement ---------------------------------------------------------
    def shard_feeds(self, feeds):
        """device_put a {name: array} feed dict per ``feed_spec``.
        Raises on a batch dim that does not divide the data axes — the
        same contract as data-parallel batch sharding. An array already
        carrying its target sharding passes through untouched — the
        device-side double-buffer path (``Executor.feed_stage`` staging
        batch N+1 in the prefetch worker) relies on this to keep the
        H2D hop off the step's critical path."""
        out = {}
        for k, v in feeds.items():
            def put(x, k=k):
                sp = self.feed_spec(k, np.ndim(x))
                shape = np.shape(x)
                for d, entry in enumerate(sp):
                    if entry is None:
                        continue
                    if d >= len(shape):
                        raise EnforceNotMet(
                            f"ShardingSpec feed entry for {k!r} shards "
                            f"dim {d} but the fed array has shape "
                            f"{tuple(shape)}")
                    n = self.axis_extent(entry)
                    if n > 1 and shape[d] % n != 0:
                        raise EnforceNotMet(
                            f"feed {k!r} batch dim {d} ({shape[d]}) "
                            f"is not divisible by the {n}-device "
                            f"{_entry_axes(entry)} mesh axes")
                target = NamedSharding(self.mesh, sp)
                s = getattr(x, "sharding", None)
                if s is not None:
                    try:
                        if s == target or s.is_equivalent_to(
                                target, np.ndim(x)):
                            return x
                    except Exception:
                        pass
                return jax.device_put(x, target)
            out[k] = jax.tree.map(put, v)
        return out

    def place_state(self, state):
        """device_put a flat {name: value} state dict per the spec."""
        out = {}
        for n, v in state.items():
            sh = self.param_sharding(n)

            def put(x, n=n, sh=sh):
                self.validate_leaf(n, np.shape(x))
                return jax.device_put(x, sh)
            out[n] = jax.tree.map(put, v)
        return out

    # -- pytree (functional-path) currency ---------------------------------
    def tree_specs(self, tree):
        """PartitionSpec pytree for a params pytree: each leaf is looked
        up by its "a/b/0" tree path through the same exact-name + rule
        table (the functional trainers' currency)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        return jax.tree_util.tree_unflatten(
            treedef, [self.param_spec(_leaf_path(p)) for p, _ in flat])

    def tree_shardings(self, tree):
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                            self.tree_specs(tree),
                            is_leaf=lambda s: isinstance(s, P))

    def place_tree(self, tree):
        """device_put a params pytree per the spec (divisibility-
        checked)."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        placed = []
        for p, x in flat:
            name = _leaf_path(p)
            sp = self.validate_leaf(name, np.shape(x))
            placed.append(jax.device_put(
                x, NamedSharding(self.mesh, sp)))
        return jax.tree_util.tree_unflatten(treedef, placed)

    # -- checkpoint interop (PR 6 reshard planner) -------------------------
    def checkpoint_axes(self, state):
        """Derive ``CheckpointManager.save(axes=)`` annotations from
        this spec: a pytree congruent to ``state`` with, per leaf, the
        dimension index it is sharded on (single named axis) or None
        (replicated / trivially tiled by size-1 axes).

        Multi-axis tilings — one dim over an axis TUPLE, or two sharded
        dims — raise ``CheckpointTopologyError``: the re-slice planner
        covers single-named-axis tilings only, and a wrong annotation
        would make an elastic restore silently concatenate shards along
        the wrong dim.
        """
        from paddle_tpu.io_checkpoint import CheckpointTopologyError
        flat, treedef = jax.tree_util.tree_flatten_with_path(state)
        axes = []
        for p, x in flat:
            name = _leaf_path(p)
            sp = self.param_spec(name)
            sharded = [(d, entry) for d, entry in enumerate(sp)
                       if entry is not None
                       and self.axis_extent(entry) > 1]
            if not sharded:
                axes.append(None)
                continue
            if len(sharded) > 1:
                raise CheckpointTopologyError(
                    f"cannot derive save(axes=) for {name!r}: spec "
                    f"{sp} tiles {len(sharded)} dimensions — the "
                    f"reshard planner covers single-named-axis params "
                    f"only")
            d, entry = sharded[0]
            names = _entry_axes(entry)
            if len(names) > 1:
                raise CheckpointTopologyError(
                    f"cannot derive save(axes=) for {name!r}: spec "
                    f"{sp} tiles dim {d} over the axis tuple {names} — "
                    f"the reshard planner covers single-named-axis "
                    f"params only")
            axes.append(d)
        return jax.tree_util.tree_unflatten(treedef, axes)

    def __repr__(self):
        return (f"ShardingSpec(mesh={dict(self.mesh.shape)}, "
                f"params={len(self.params)}, rules={len(self.rules)}, "
                f"feeds={len(self.feeds)}, "
                f"feed_batch_axes={self.feed_batch_axes})")
