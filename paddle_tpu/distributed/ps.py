"""Parameter-server runtime: server, client, async Communicator.

Parity targets (SURVEY §2.6/§3.3): the reference's RPC substrate
(operators/distributed/rpc_client.h:33 AsyncSendVar/AsyncGetVar/
AsyncPrefetchVar/barriers/checkpoint-notify, request handlers
request_handler_impl.cc), the listen_and_serv op
(distributed_ops/listen_and_serv_op.cc:330 — RunSyncLoop fan-in →
optimize blocks → barrier → serve gets; RunAsyncLoop per-var update on
arrival), the async Communicator (distributed/communicator.h:160 —
background send threads with gradient merging), sparse parameter
prefetch (distributed/parameter_prefetch.cc), and checkpoint notify
(distributed_ops/checkpoint_notify_op.cc).

TPU-native shape: dense data-parallelism belongs to SPMD/XLA collectives
(paddle_tpu.parallel); the PS path remains for what genuinely needs a
host-side service — giant/growing sparse tables and asynchronous
trainers. The transport is the fixed-schema framed binary protocol in
wire.py over persistent connections (the role of grpc_client.cc's
bytebuffer serde; NO pickle — socket bytes are never evaluated), with
retry/backoff + per-client request-sequence dedup on the client
(rpc_client.h:33 contract, grpc_client.cc retry path). The "optimize
block" the reference executes per parameter is the same functional
`Optimizer` rule the local executor uses, applied server-side.

Sync semantics (RunSyncLoop parity): each var carries a round counter.
``pull(name, min_round)`` blocks until the server has applied that many
rounds; trainers push grads for round r+1, the server averages the
fan-in of all trainers and steps the optimizer, then wakes pullers.
Round 0 is the server-side initial value, so every trainer starts from
identical parameters (the reference broadcasts startup from pserver the
same way).

Fault tolerance (docs/ELASTIC_TRAINING.md "Pserver failover"): a
pserver's hosted state snapshots to generation-tagged artifact sets
published through ``io_checkpoint``'s integrity machinery (per-array
CRC32 manifest, mkstemp + fsync + atomic ``os.replace``), periodically
on a background thread (``start_snapshots``) off the request path. A
restarted server (``run_pserver`` under ``launch_ps
--ps_snapshot_secs``) warm-boots from the newest generation that
VERIFIES — a torn/bit-rotted one is quarantined (``*.corrupt``) and
the restore walks back. Every server carries a random ``incarnation``
token served via the ``SERVER_INFO`` frame; ``PSClient`` probes it on
every reconnect, so a client that outlives a server restart detects
the new incarnation, counts the optimizer rounds lost since the last
snapshot (``ps_stale_rounds_total``), and re-establishes its sync-mode
round expectations instead of deadlocking on a round the reborn server
will never reach.
"""

import collections
import json
import logging
import os
import re
import socket
import socketserver
import threading
import time

import numpy as np

from paddle_tpu.core.enforce import enforce
from paddle_tpu.core.flags import define_flag, get_flag
from paddle_tpu.distributed import wire
from paddle_tpu.monitor import goodput as _goodput
from paddle_tpu.monitor.registry import counter as _counter
from paddle_tpu.monitor.registry import gauge as _gauge
from paddle_tpu.monitor.registry import histogram as _histogram

__all__ = ["ParameterServer", "NativeParameterServer", "PSClient",
           "Communicator", "run_pserver", "make_parameter_server"]

_m_snap_saves = _counter(
    "ps_snapshot_saves_total",
    "Pserver snapshot generations made durable (periodic background "
    "snapshots + checkpoint-notify + final flush)")
_m_snap_ms = _histogram(
    "ps_snapshot_ms",
    "Wall ms to make one pserver snapshot generation durable "
    "(state export under the table/var locks + integrity-manifested "
    "atomic publish)")
_m_reconnects = _counter(
    "ps_client_reconnects_total",
    "PSClient calls that survived at least one dropped/refused pserver "
    "connection (retried with backoff; mutating frames stay "
    "exactly-once via the (client_id, seq) dedup)")
_m_stale_rounds = _counter(
    "ps_stale_rounds_total",
    "Optimizer rounds a restarted pserver lost between its last "
    "snapshot and the crash, as observed by reconnecting clients "
    "re-establishing their sync-round expectations")
_m_epoch = _gauge(
    "ps_epoch",
    "Committed fleet-membership epoch this pserver serves (0 = the "
    "implicit static-placement epoch: no resize has ever committed)")
_m_table_bytes = _gauge(
    "ps_sparse_table_bytes",
    "Host-resident bytes of each hosted sparse table's row store "
    "(float32 rows + adagrad accumulators; native and Python stores "
    "count the same payload), refreshed at every snapshot generation",
    labels=("table",))
_m_migrated = _counter(
    "ps_migrated_rows_total",
    "Sparse rows + dense vars this pserver adopted across committed "
    "fleet-resize migrations (counted once, at MIGRATE_COMMIT / the "
    "warm-boot epoch reconcile)")


def _migrate_fault_point(stage, path=None):
    """Migration chaos hook: no-op in production. The stage boundaries
    the elastic protocol crosses — \"plan\" (source, before freezing),
    \"chunk\" (source, before streaming a unit), \"staged\" (target,
    after publishing a unit's durable shadow; ``path`` names it) and
    \"commit\" (any server, at MIGRATE_COMMIT entry) — are exactly the
    points ``testing.faults.install_ps_migrate_faults`` patches to
    crash (PT_FAULT_PS_MIGRATE_CRASH) or tear a staged shadow
    (PT_FAULT_PS_MIGRATE_TORN)."""


define_flag("ps_transport", "auto",
            "PS server transport: auto (C++ when the hosted state is "
            "expressible, else Python), native (require C++), python")


def _stop_grace_seconds():
    """How long a server keeps accepting after a STOP frame before the
    listener closes. The trainer that sends STOP has finished, but
    another trainer's final-barrier reply may still be in flight; a
    client needing a retry in that window must be able to reconnect —
    immediate listener close turns the race into ECONNREFUSED at the
    end of an otherwise-successful run. PT_PS_STOP_GRACE overrides
    (seconds)."""
    try:
        v = float(os.environ.get("PT_PS_STOP_GRACE", "0.5"))
    except ValueError:
        return 0.5
    # clamp: a negative value must mean 'no grace', and inf/nan would
    # turn shutdown into a hang (sleep(-1) raises in the daemon thread
    # on the Python path; a negative cast through c_uint64 wraps to
    # ~forever on the native path)
    import math as _math
    if not _math.isfinite(v):
        return 0.5
    return min(max(v, 0.0), 60.0)


# framing delegates to the single shared implementation in wire.py
_recv_exact = wire.recv_exact
_send_frame = wire.send_frame
_recv_frame = wire.recv_frame

#: the SERVER reply path, separated from the client-side _send_frame so
#: testing/faults' wire chaos (reply drop / delay) can patch exactly
#: the server side of the conversation and nothing else
_reply_frame = wire.send_frame

#: the pserver snapshot filename grammar, in ONE place —
#: testing/faults and tools/fsck_checkpoint parse the same names
#: _ps_checkpoint_save writes, and a format change must break loudly
#: there, not silently no-op the fault injection / fsck verdicts
PS_GEN_META_RE = re.compile(r"^pserver_(.+)\.gen(\d+)\.json$")
PS_GEN_ARTIFACT_RE = re.compile(r"^pserver_(.+)\.gen(\d+)\.npz$")

#: the dense-artifact slot-array key prefix (``__slot__/<var>/<slot>``)
_SLOT_KEY_PREFIX = "__slot__/"


def _ps_log(msg):
    """Loud pserver-lifecycle line: straight to stderr (the launcher's
    serverlog), like the launcher's own ``[launch]`` idiom — warm-boot
    and quarantine evidence must be greppable even when the worker
    never configured logging."""
    import sys
    print(f"[pserver] {msg}", file=sys.stderr, flush=True)


def _ps_tag(host, port):
    return f"{host}_{port}".replace(".", "_")


def _ps_dense_path(dirname, tag, gen):
    return os.path.join(dirname, f"pserver_{tag}.gen{gen}.npz")


def _ps_table_path(dirname, tag, table, gen):
    return os.path.join(dirname, f"pserver_{tag}_{table}.gen{gen}.npz")


def _ps_meta_path(dirname, tag, gen):
    return os.path.join(dirname, f"pserver_{tag}.gen{gen}.json")


def _ps_gen_files(dirname, tag, gen, tables):
    """Every file a complete generation comprises (meta last)."""
    return ([_ps_dense_path(dirname, tag, gen)]
            + [_ps_table_path(dirname, tag, t, gen) for t in tables]
            + [_ps_meta_path(dirname, tag, gen)])


def _ps_listdir(dirname):
    """``os.listdir`` under the blip-is-not-corruption rule: a
    transient OSError is retried and then RE-RAISED — swallowing it
    into an empty listing would make a warm boot silently restore
    nothing (discarding training) and a save reuse a generation
    number it couldn't see. ``FileNotFoundError`` (dir never created:
    no snapshots yet) is genuinely empty."""
    from paddle_tpu import io_checkpoint as ioc
    try:
        return ioc._retry_transient(
            lambda: os.listdir(dirname),
            f"pserver snapshot dir {dirname} list")
    except FileNotFoundError:
        return []


def _ps_complete_gens(dirname, tag):
    """Sorted ``[(gen, meta), ...]`` of generations with a parseable
    meta AND every artifact it promises on disk — the generations a
    warm boot will consider (the PR-5 complete-step rule: the meta is
    published LAST, so a crash mid-snapshot can never yield a
    half-generation that looks whole). A garbage meta CONTENT
    (ValueError/TypeError) makes its generation invisible, like a
    torn ``ckpt_N.json``; a transient I/O error re-raises — dropping
    the newest generation over a blip would silently rewind the warm
    boot (``run_pserver`` crashes into the restart budget instead)."""
    from paddle_tpu import io_checkpoint as ioc
    meta_re = re.compile(rf"^pserver_{re.escape(tag)}\.gen(\d+)\.json$")
    out = []
    for f in _ps_listdir(dirname):
        m = meta_re.match(f)
        if not m:
            continue
        gen = int(m.group(1))

        def read_meta(fname=f):
            with open(os.path.join(dirname, fname)) as fh:
                return json.load(fh)

        try:
            meta = ioc._retry_transient(
                read_meta, f"pserver snapshot meta {f} read")
            tables = list(meta.get("tables", []))
        except FileNotFoundError:
            continue            # pruned under us
        except (ValueError, TypeError):
            continue            # garbage content: never complete
        promised = _ps_gen_files(dirname, tag, gen, tables)[:-1]
        if all(ioc._stat_exists(p) for p in promised):
            out.append((gen, meta))
    return sorted(out)


def _ps_next_gen(dirname, tag):
    """One past the highest generation index ANY matching file (meta,
    artifact, or quarantined ``*.corrupt``) has ever used — a
    quarantined generation's number is never reused, so its evidence
    files can't collide with a later healthy publish. A persistent
    listing error re-raises (via ``_ps_listdir``): guessing 0 would
    silently overwrite whatever the listing failed to show."""
    pat = re.compile(
        rf"^pserver_{re.escape(tag)}(?:_.+)?\.gen(\d+)\.(?:npz|json)$")
    best = -1
    for f in _ps_listdir(dirname):
        if f.endswith(".corrupt"):
            f = f[:-len(".corrupt")]
        m = pat.match(f)
        if m:
            best = max(best, int(m.group(1)))
    return best + 1


def _ps_sweep_tmps(dirname, tag):
    """Remove a killed previous incarnation's publish temps
    (``.pserver_<tag>*.tmp.npz`` / this tag's meta temps). The
    supervisor guarantees the previous incarnation of THIS endpoint is
    dead before a respawn, so same-tag temps are stale by
    construction; other endpoints' in-flight temps are never touched."""
    try:
        names = os.listdir(dirname)
    except OSError:
        return
    for f in names:
        # the tag must end at a '.' (dense/meta artifact) or '_'
        # (table artifact): launch_ps puts EVERY pserver's snapshots
        # in one shared ps_state dir, and a bare prefix match would
        # let tag "..._1234" sweep a live sibling "..._12345"'s
        # in-flight publish temp out from under its writer
        mine = (f.startswith((f".pserver_{tag}.", f".pserver_{tag}_"))
                and f.endswith((".tmp.npz", ".json.tmp")))
        if not mine:
            continue
        try:
            os.remove(os.path.join(dirname, f))
        except OSError:
            pass


def _ps_publish_json(path, obj):
    """fsync'd atomic JSON publish (io_checkpoint's one shared
    idiom; the ``.{basename}.`` temp prefix is what _ps_sweep_tmps
    and fsck recognize)."""
    from paddle_tpu import io_checkpoint as ioc
    ioc._publish_json_atomic(path, obj,
                             prefix=f".{os.path.basename(path)}.")
    ioc._fsync_dir(os.path.dirname(path) or ".")


def _ps_checkpoint_save(dirname, host, port, dense, sparse_tables,
                        incarnation=0, keep=2, epoch=0, shard_map=None):
    """The pserver checkpoint artifact contract, shared by BOTH
    transports (cross-transport restore depends on it): one
    generation-tagged artifact set per save —
    ``pserver_<tag>.gen<G>.npz`` holding {name: value} plus per-var
    optimizer slots (``__slot__/<var>/<slot>`` keys) and round/step
    counters in the manifest body, one ``pserver_<tag>_<table>.gen<G>
    .npz`` per sparse table with ids/rows/accum (kCheckpointBlockId
    parity, listen_and_serv_op.cc:345), and a ``.gen<G>.json`` meta
    marker published LAST — a generation without its meta is invisible
    to restore, so a crash mid-save can never look whole. Every npz
    publishes through ``io_checkpoint.publish_npz`` (per-array CRC32
    manifest, mkstemp + fsync + atomic ``os.replace``); the newest
    ``keep`` complete generations survive pruning — the walk-back
    budget a corrupt newest generation falls back into.

    ``dense`` is the ``_dense_export()`` triple
    ``(values, var_state, slots)``: values {name: array}, var_state
    {name: (round, step_count)}, slots {name: {slot: array}}."""
    from paddle_tpu import io_checkpoint as ioc
    os.makedirs(dirname, exist_ok=True)
    tag = _ps_tag(host, port)
    gen = _ps_next_gen(dirname, tag)
    values, var_state, slots = dense
    arrays = {n: v for n, v in values.items()}
    for n, sl in slots.items():
        for k, a in sl.items():
            arrays[f"{_SLOT_KEY_PREFIX}{n}/{k}"] = a
    body = {
        "kind": "pserver_dense",
        "endpoint": tag,
        "gen": gen,
        "incarnation": int(incarnation),
        "var_state": {n: {"round": int(r), "step": int(s)}
                      for n, (r, s) in var_state.items()},
    }
    ioc.publish_npz(_ps_dense_path(dirname, tag, gen), arrays, body)
    for n, t in sorted(sparse_tables.items()):
        ids, rows, accum = t.snapshot()
        ioc.publish_npz(
            _ps_table_path(dirname, tag, n, gen),
            {"ids": ids, "rows": rows, "accum": accum},
            {"kind": "pserver_table", "endpoint": tag, "table": n,
             "gen": gen})
    meta = {
        "gen": gen, "endpoint": tag, "incarnation": int(incarnation),
        "tables": sorted(sparse_tables), "time": time.time(),
        # fleet-membership record (docs/ELASTIC_TRAINING.md "Resizing
        # the pserver fleet"): a snapshot taken at epoch E restores
        # into epoch E — the warm-boot reconcile and fsck's
        # --num-servers verdict both read these
        "epoch": int(epoch),
    }
    if shard_map is not None:
        meta["shard_map"] = shard_map
    _ps_publish_json(_ps_meta_path(dirname, tag, gen), meta)
    # prune: meta FIRST (a killed prune must leave meta-less artifacts
    # — invisible to restore — never a meta promising missing files)
    complete = _ps_complete_gens(dirname, tag)
    for g, m in (complete[:-keep] if keep else []):
        files = _ps_gen_files(dirname, tag, g,
                              list(m.get("tables", [])))
        for p in [files[-1]] + files[:-1]:
            try:
                os.remove(p)
            except OSError:
                pass
    return gen


def _ps_quarantine_gen(dirname, tag, gen, tables):
    """Rename a generation's meta + artifacts ``*.corrupt`` (the
    restore walk-back's quarantine — evidence preserved, never offered
    for restore again; its generation number is never reused)."""
    renamed = []
    files = _ps_gen_files(dirname, tag, gen, tables)
    # meta first: a crash mid-quarantine leaves meta-less artifacts,
    # which restore already ignores
    for p in [files[-1]] + files[:-1]:
        try:
            os.replace(p, p + ".corrupt")
            renamed.append(os.path.basename(p) + ".corrupt")
        except OSError:
            pass
    return renamed


def _ps_load_legacy(dirname, tag, apply_dense, sparse_tables):
    """The pre-generation artifact layout (plain
    ``pserver_<tag>.npz`` + ``pserver_<tag>_<table>.npz``): verified
    when a manifest is present, accepted structurally otherwise; a
    torn artifact is quarantined and restore proceeds without it
    (there is nothing older to walk back to in the legacy layout)."""
    from paddle_tpu import io_checkpoint as ioc
    restored = False
    path = os.path.join(dirname, f"pserver_{tag}.npz")
    if os.path.exists(path):
        try:
            _, arrays = ioc.verify_npz(path)
        except ioc.CheckpointCorruptError as e:
            _ps_log(f"quarantined corrupt legacy artifact {path}: {e}")
            ioc._m_corrupt.inc()
            try:
                os.replace(path, path + ".corrupt")
            except OSError:
                pass
        else:
            for n, v in arrays.items():
                if not n.startswith(_SLOT_KEY_PREFIX):
                    apply_dense(n, v, None, None)
            restored = True
    for n, t in sparse_tables.items():
        p = os.path.join(dirname, f"pserver_{tag}_{n}.npz")
        if not os.path.exists(p):
            continue
        try:
            _, arrays = ioc.verify_npz(p)
        except ioc.CheckpointCorruptError as e:
            _ps_log(f"quarantined corrupt legacy artifact {p}: {e}")
            ioc._m_corrupt.inc()
            try:
                os.replace(p, p + ".corrupt")
            except OSError:
                pass
            continue
        t.restore(arrays["ids"], arrays["rows"],
                  arrays.get("accum"))
        restored = True
    return {"gen": None, "legacy": True} if restored else None


def _ps_checkpoint_load(dirname, host, port, apply_dense,
                        sparse_tables, make_table=None):
    """Counterpart of ``_ps_checkpoint_save``: restore the newest
    complete generation that VERIFIES, walking back past corrupt ones.

    Calls ``apply_dense(name, value, state, slots)`` per hosted dense
    var found in the artifact (``state`` = (round, step_count) or
    None; ``slots`` = {slot: array} or None) and restores each sparse
    table (old artifacts without accum restore with empty accumulators
    so stale G cannot scale the rows). A generation whose any artifact
    fails integrity verification is QUARANTINED (every file renamed
    ``*.corrupt``, ``corrupt_checkpoints_total``++) and the previous
    one restores — one rotted file never bricks the warm boot. A
    transient ``OSError`` persisting through retries re-raises
    unchanged (blip is not corruption: crash into the supervisor's
    restart budget rather than quarantine a healthy snapshot). Falls
    back to the legacy un-generational layout when no generation
    exists. Returns the restored generation's meta, or None when
    nothing restorable was found."""
    from paddle_tpu import io_checkpoint as ioc
    tag = _ps_tag(host, port)
    gens = _ps_complete_gens(dirname, tag)
    if not gens:
        return _ps_load_legacy(dirname, tag, apply_dense,
                               sparse_tables)
    quarantined = 0
    for gen, meta in reversed(gens):
        tables = list(meta.get("tables", []))
        try:
            manifest, arrays = ioc.verify_npz(
                _ps_dense_path(dirname, tag, gen))
            table_blobs = {}
            for t in tables:
                _, tb = ioc.verify_npz(
                    _ps_table_path(dirname, tag, t, gen))
                table_blobs[t] = tb
        except ioc.CheckpointCorruptError as e:
            ioc._m_corrupt.inc()
            renamed = _ps_quarantine_gen(dirname, tag, gen, tables)
            quarantined += 1
            _ps_log(f"quarantined corrupt snapshot generation {gen} "
                    f"({', '.join(renamed) or 'nothing renamed'}): "
                    f"{e}; walking back")
            continue
        var_state = (manifest or {}).get("var_state", {})
        slots = {}
        for key, a in arrays.items():
            if not key.startswith(_SLOT_KEY_PREFIX):
                continue
            name, slot = key[len(_SLOT_KEY_PREFIX):].rsplit("/", 1)
            slots.setdefault(name, {})[slot] = a
        for n, v in arrays.items():
            if n.startswith(_SLOT_KEY_PREFIX):
                continue
            st = var_state.get(n)
            state = ((int(st["round"]), int(st["step"]))
                     if st else None)
            apply_dense(n, v, state, slots.get(n))
        for t, tb in table_blobs.items():
            table = sparse_tables.get(t)
            if table is None and make_table is not None:
                # elastic warm boot: the table was adopted via
                # migration (hosted from a recipe, not the program),
                # so re-create it before restoring its rows
                table = make_table(t)
            if table is None:
                continue        # table not hosted here
            table.restore(tb["ids"], tb["rows"], tb.get("accum"))
        if quarantined:
            _ps_log(f"restored from last-good snapshot generation "
                    f"{gen} after quarantining {quarantined} corrupt "
                    f"newer generation(s)")
        return meta
    _ps_log(f"every snapshot generation in {dirname} for {tag} was "
            f"corrupt ({quarantined} quarantined); starting from "
            f"initial values")
    return None


class _UnitRetired(Exception):
    """The hosted unit was migrated away mid-request (elastic resize
    committed while this request waited): the handler converts this
    into a WRONG_EPOCH reply, and the client re-routes via the new
    shard map — nothing was applied here."""


class _DenseVar:
    """One hosted parameter: value + optimizer state + round counter.

    The update mirrors the local executor's per-param optimize op
    (optimizer.py _apply_optimizer_compute) exactly: per-param
    regularizer then lr * param_lr then the optimizer rule — and NO
    gradient clipping here, because the trainer program keeps its
    clip_grads op and clips before sending (fluid clips trainer-side in
    PS mode too)."""

    def __init__(self, value, optimizer, regularizer=None, param_lr=1.0):
        self.value = np.asarray(value)
        self.optimizer = optimizer
        self.regularizer = regularizer
        self.param_lr = param_lr
        self.slots = None              # lazy: built on first update
        self.step_count = 0
        self.round = 0
        self.accum = None              # sum of grads this round
        self.pushed = set()            # trainer ids seen this round
        self.evicted = False           # migrated away (elastic resize)
        self.cv = threading.Condition()
        self._native = None            # (lib, kind) once probed

    # -- native dense optimize block --------------------------------------
    # The server-side update runs in C++ for the common rules
    # (SGD/Momentum/Adam [+ L1/L2 decay]), like the reference's pserver
    # optimize sub-block (request_handler_impl.cc -> C++ optimizer op
    # kernels). LR schedules still evaluate in Python per step; exotic
    # optimizers/regularizers fall back to the jnp path below.

    def _native_kind(self):
        if self._native is not None:
            return self._native
        self._native = (None, None)
        from paddle_tpu import optimizer as po
        opt = self.optimizer
        # exact type, not isinstance: subclasses (DGC momentum, …)
        # define different updates and must take the jnp path
        kind = None
        if type(opt) is po.SGDOptimizer:
            kind = "sgd"
        elif type(opt) is po.MomentumOptimizer:
            kind = "momentum"
        elif type(opt) is po.AdamOptimizer:
            kind = "adam"
        reg = self.regularizer or (opt.regularization if opt else None)
        if reg is not None:
            from paddle_tpu.regularizer import (L1DecayRegularizer,
                                                L2DecayRegularizer)
            if type(reg) not in (L1DecayRegularizer,
                                 L2DecayRegularizer):
                kind = None
        if (kind is not None and self.value.dtype == np.float32
                and self.value.flags.c_contiguous):
            try:
                from paddle_tpu import native
                self._native = (native.get_lib(), kind)
            except Exception:
                pass
        return self._native

    def _step_native(self, lib, kind, grad):
        import ctypes
        fp = ctypes.POINTER(ctypes.c_float)

        def ptr(a):
            return a.ctypes.data_as(fp)

        opt = self.optimizer
        n = self.value.size
        grad = np.ascontiguousarray(grad, np.float32)
        # the kernels write a fresh buffer from the old one and the
        # reference swaps under the caller-held cv: pull() hands out
        # self.value zero-copy and encodes it outside the lock, so a
        # step must never mutate a buffer a puller may still be
        # reading — the jnp path's swap semantics at in-place traffic.
        # The previous step's retired buffer is recycled when the
        # refcount PROVES no puller still holds it (a fresh 64 MB
        # np.empty costs a full page-fault-zeroing pass per step
        # otherwise); a held buffer is simply dropped to the allocator.
        import sys as _sys
        p_in = self.value
        spare, self._spare = getattr(self, "_spare", None), None
        if (spare is not None and spare.shape == p_in.shape
                and _sys.getrefcount(spare) == 2):  # local ref only
            p_out = spare
        else:
            p_out = np.empty_like(p_in)
        reg = self.regularizer or opt.regularization
        if reg is not None:
            from paddle_tpu.regularizer import L2DecayRegularizer
            if grad.base is not None or not grad.flags.owndata:
                grad = grad.copy()
            fn = (lib.pt_dense_l2_decay
                  if isinstance(reg, L2DecayRegularizer)
                  else lib.pt_dense_l1_decay)
            fn(ptr(grad), ptr(p_in), n, reg.coeff)
        # constant lr stays jax-free (the common PS case); only
        # callable schedules evaluate through _lr_value
        if callable(opt.learning_rate):
            lr = float(opt._lr_value(np.float32(self.step_count)))
        else:
            lr = float(opt.learning_rate)
        lr *= self.param_lr
        if kind == "sgd":
            lib.pt_dense_sgd(ptr(p_out), ptr(p_in), ptr(grad), n, lr)
        else:
            if self.slots is None:
                self.slots = {k: np.zeros_like(p_in)
                              for k in opt._slot_defaults}
            if kind == "momentum":
                lib.pt_dense_momentum(
                    ptr(p_out), ptr(p_in), ptr(self.slots["velocity"]),
                    ptr(grad), n, lr, opt.momentum,
                    int(bool(getattr(opt, "use_nesterov", False))))
            else:
                lib.pt_dense_adam(
                    ptr(p_out), ptr(p_in), ptr(self.slots["moment1"]),
                    ptr(self.slots["moment2"]), ptr(grad), n, lr,
                    opt.beta1, opt.beta2, opt.epsilon, self.step_count)
        self.value = p_out
        self._spare = p_in      # next step reuses it if nobody holds it

    def _step(self, grad):
        opt = self.optimizer
        if opt is None:
            return
        self.step_count += 1
        lib, kind = self._native_kind()
        if lib is not None:
            return self._step_native(lib, kind, grad)
        import jax.numpy as jnp
        p = jnp.asarray(self.value)
        g = jnp.asarray(grad)
        if self.slots is None:
            self.slots = opt._slots(p)
        t = jnp.asarray(self.step_count, jnp.int32)
        reg = self.regularizer or opt.regularization
        if reg is not None:
            g = reg(p, g)
        lr = opt._lr_value(t.astype(jnp.float32)) * self.param_lr
        new_p, self.slots = opt._update(p, g, self.slots, lr, t)
        self.value = np.asarray(new_p)

    def _accumulate(self, grad):
        """Sync fan-in accumulation (listen_and_serv's grad
        aggregation): first push owns a fresh float32 buffer,
        subsequent pushes add in place via the native kernel when
        available (numpy otherwise)."""
        if self.accum is None:
            self.accum = np.array(grad, np.float32, copy=True)
            return
        enforce(np.shape(grad) == self.accum.shape,
                f"grad shape {np.shape(grad)} does not match hosted "
                f"var shape {self.accum.shape}")
        lib, _ = self._native_kind()
        if (lib is not None and self.accum.flags.c_contiguous
                and grad.dtype == np.float32):
            import ctypes
            fp = ctypes.POINTER(ctypes.c_float)
            g = np.ascontiguousarray(grad, np.float32)
            lib.pt_dense_accum(self.accum.ctypes.data_as(fp),
                               g.ctypes.data_as(fp), self.accum.size)
        else:
            self.accum = self.accum + grad

    def push_sync(self, trainer_id, grad, num_trainers, timeout=120.0):
        with self.cv:
            if self.evicted:
                raise _UnitRetired("var migrated away")
            if trainer_id in self.pushed:
                # stale duplicate (e.g. retry) — wait for next round
                ok = self.cv.wait_for(
                    lambda: trainer_id not in self.pushed
                    or self.evicted, timeout=timeout)
                enforce(ok, f"duplicate push from trainer {trainer_id} "
                            f"timed out waiting for round fan-in")
                if self.evicted:
                    # the round this duplicate waited on (including
                    # this trainer's FIRST push) migrated verbatim —
                    # this push re-routes and applies at the new owner
                    raise _UnitRetired("var migrated away mid-fan-in")
            self._accumulate(grad)
            self.pushed.add(trainer_id)
            if len(self.pushed) >= num_trainers:
                self._step(self.accum / max(num_trainers, 1))
                self.accum = None
                self.pushed.clear()
                self.round += 1
                self.cv.notify_all()

    def push_async(self, grad):
        with self.cv:
            self._step(grad)
            self.round += 1
            self.cv.notify_all()

    def pull(self, min_round, timeout=120.0):
        with self.cv:
            ok = self.cv.wait_for(
                lambda: self.round >= min_round or self.evicted,
                timeout=timeout)
            enforce(ok, f"pull timed out waiting for round {min_round}")
            if self.evicted and self.round < min_round:
                # the rounds this pull waits for will complete at the
                # NEW owner (partial fan-in state migrated verbatim)
                raise _UnitRetired("var migrated away mid-round")
            return self.value


class _SparseTable:
    """Hosted sparse table (lookup_sparse_table / pserver sparse block
    parity): rows materialize on first touch; pushes apply the table's
    optimizer rule — "sgd" or "adagrad" (the pserver optimize-block
    choices the reference runs for sparse params).

    With the default initializer and the native library built, the row
    store and updates run in C++ (native/src/ps_table.cc — the sparse
    host path SURVEY §2.6/§7 keeps hand-written C++); a custom Python
    initializer falls back to the Python store."""

    def __init__(self, dim, initializer=None, seed=0, lr=1.0,
                 optimizer="sgd", eps=1e-6):
        enforce(optimizer in ("sgd", "adagrad"),
                f"sparse optimizer must be sgd|adagrad, got {optimizer!r}")
        self.dim = dim
        self.lr = lr
        self.optimizer = optimizer
        self.eps = eps
        self._native = None
        if initializer is None:
            try:
                from paddle_tpu import native
                if native.available():
                    self._native = native.NativeSparseTable(
                        dim, optimizer, lr, eps, seed)
            except Exception:
                self._native = None
        self.rows = {}
        self.accum = {}               # adagrad per-row G accumulators
        self._step = 0                # pull/push call counter (shrink)
        self._touch = {}              # row id -> last touching step
        self._rng = np.random.RandomState(seed)
        self._init = initializer or (
            lambda rng, dim: rng.normal(0, 0.01, dim).astype(np.float32))
        self.lock = threading.Lock()

    def __len__(self):
        if self._native is not None:
            return len(self._native)
        with self.lock:
            return len(self.rows)

    def nbytes(self):
        """Host-resident bytes of this table's row store: rows are
        float32[dim], adagrad doubles that with the per-row G
        accumulator. Same arithmetic for the native (C++) and Python
        stores — both hold the same float32 payload (the native store's
        hash-map overhead is not counted, matching how the ledger
        counts array payloads everywhere else)."""
        per_row = self.dim * 4 * (2 if self.optimizer == "adagrad"
                                  else 1)
        return len(self) * per_row

    def pull(self, ids):
        if self._native is not None:
            return self._native.pull(ids)
        with self.lock:
            self._step += 1
            out = np.empty((len(ids), self.dim), np.float32)
            for i, x in enumerate(ids):
                row = self.rows.get(int(x))
                if row is None:
                    row = self._init(self._rng, self.dim)
                    self.rows[int(x)] = row
                self._touch[int(x)] = self._step
                out[i] = row
            return out

    def push(self, ids, grads, lr=None):
        if self._native is not None:
            self._native.push(ids, grads, lr)
            return
        lr = self.lr if lr is None else lr
        with self.lock:
            self._step += 1
            for x, g in zip(ids, grads):
                x = int(x)
                row = self.rows.get(x)
                if row is None:
                    row = self._init(self._rng, self.dim)
                if self.optimizer == "adagrad":
                    acc = self.accum.get(x)
                    acc = (g * g if acc is None else acc + g * g)
                    self.accum[x] = acc
                    row = row - lr * g / (np.sqrt(acc) + self.eps)
                else:
                    row = row - lr * g
                self.rows[x] = row
                self._touch[x] = self._step

    def shrink(self, max_age):
        """Evict rows untouched for more than ``max_age`` pull/push
        calls (FleetWrapper::ShrinkSparseTable parity,
        fleet_wrapper.h:141). Returns evicted count."""
        if self._native is not None:
            return self._native.shrink(max_age)
        with self.lock:
            stale = [x for x in self.rows
                     if self._step - self._touch.get(x, 0) > max_age]
            for x in stale:
                self.rows.pop(x, None)
                self.accum.pop(x, None)
                self._touch.pop(x, None)
            return len(stale)

    def snapshot(self):
        """(ids, rows, accum) arrays for checkpoints."""
        if self._native is not None:
            return self._native.snapshot()
        with self.lock:
            ids = np.fromiter(self.rows, np.int64, len(self.rows))
            rows = (np.stack([self.rows[int(i)] for i in ids])
                    if len(ids) else np.zeros((0, self.dim), np.float32))
            accum = (np.stack([self.accum.get(int(i),
                                              np.zeros(self.dim,
                                                       np.float32))
                               for i in ids])
                     if len(ids) else np.zeros((0, self.dim), np.float32))
            return ids, rows, accum

    def restore(self, ids, rows, accum=None):
        if self._native is not None:
            self._native.restore(ids, rows, accum)
            return
        with self.lock:
            self.rows = {int(i): np.asarray(r, np.float32)
                         for i, r in zip(ids, rows)}
            self.accum = {}
            # mirror the native import (ps_table.cc): restored rows are
            # freshly touched, else the next shrink would evict the
            # whole just-loaded table
            self._step += 1
            self._touch = {int(i): self._step for i in ids}
            if accum is not None and len(accum):
                for i, a in zip(ids, accum):
                    a = np.asarray(a, np.float32)
                    if np.any(a):
                        self.accum[int(i)] = a


def _new_incarnation():
    """A fresh random 63-bit token per server object (nonzero; fits the
    SERVER_INFO int64 reply). Random, not PADDLE_RESTART_COUNT: two
    incarnations must never collide even across supervisor restarts
    that reset the attempt counter."""
    return (int.from_bytes(os.urandom(8), "little") & (2 ** 63 - 1)) or 1


class _SnapshotLoop:
    """Periodic async background snapshot, shared by both transports:
    a daemon thread calls ``self.save(dirname)`` every ``interval``
    seconds OFF the request path (the save itself takes each var/table
    lock only long enough to copy). ``stop_snapshots`` joins the
    thread and (by default) flushes one final generation so a graceful
    STOP never loses the tail of training."""

    _snap_thread = None

    def save(self, dirname):
        """One snapshot generation (see ``_ps_checkpoint_save``).
        Serialized per server: the background thread and a request-path
        CHECKPOINT_NOTIFY racing on the same generation number could
        otherwise publish a set whose dense and table artifacts came
        from different moments."""
        with self._save_lock:
            t0 = time.perf_counter()
            _ps_checkpoint_save(dirname, self.host, self.port,
                                self._dense_export(), self.sparse,
                                incarnation=self.incarnation,
                                epoch=getattr(self, "epoch", 0),
                                shard_map=getattr(self, "shard_map",
                                                  None))
            _m_snap_saves.inc()
            _m_snap_ms.observe((time.perf_counter() - t0) * 1e3)
            # snapshot cadence doubles as the sparse-table memory
            # accounting tick: cheap (len * row bytes), off the
            # request path, and fresh enough for capacity planning
            try:
                for name, tbl in self.sparse.items():
                    _m_table_bytes.set(tbl.nbytes(), table=name)
            except Exception:
                pass

    def start_snapshots(self, dirname, interval=5.0):
        enforce(self._snap_thread is None, "snapshots already started")
        enforce(interval > 0, f"snapshot interval must be > 0 "
                              f"(got {interval})")
        os.makedirs(dirname, exist_ok=True)
        _ps_sweep_tmps(dirname, _ps_tag(self.host, self.port))
        self._snap_dir = dirname
        self._snap_stop = threading.Event()

        def loop():
            while not self._snap_stop.wait(interval):
                try:
                    self.save(dirname)
                except Exception as e:
                    # a snapshot failure must never kill the serving
                    # loop it protects; the next interval retries
                    _ps_log(f"snapshot failed (will retry next "
                            f"interval): {type(e).__name__}: {e}")

        self._snap_thread = threading.Thread(
            target=loop, daemon=True, name="pt-ps-snapshot")
        self._snap_thread.start()
        return self

    def stop_snapshots(self, final_save=True, timeout=30.0):
        if self._snap_thread is None:
            return
        self._snap_stop.set()
        t = self._snap_thread
        t.join(timeout)
        self._snap_thread = None
        if t.is_alive():
            # a save wedged in I/O still HOLDS _save_lock: attempting
            # the final flush would block this (shutdown) path on that
            # lock forever — skip it loudly instead; the wedged save
            # may still land on its own
            _ps_log(f"snapshot thread did not stop within {timeout}s "
                    f"(a save is wedged in I/O); skipping the final "
                    f"flush rather than blocking shutdown on its lock")
            return
        if final_save:
            try:
                self.save(self._snap_dir)
            except Exception as e:
                _ps_log(f"final snapshot failed: "
                        f"{type(e).__name__}: {e}")


def _row_chunks(ids, rows, accum):
    """Split one vshard's rows into wire-sized chunks (ids/rows/accum
    sliced together). Always returns at least one chunk so an empty
    vshard still stages a (valid, empty) shadow at the target."""
    if ids.size == 0:
        return [{"ids": ids, "rows": rows, "accum": accum}]
    per_row = int(rows.itemsize
                  * (rows.shape[1] if rows.ndim > 1 else 1)) * 2 + 8
    cap_bytes = max(1, min(wire.max_message_bytes() // 2, 4 << 20))
    cap = max(1, cap_bytes // max(per_row, 1))
    return [{"ids": ids[i:i + cap], "rows": rows[i:i + cap],
             "accum": accum[i:i + cap]}
            for i in range(0, int(ids.size), cap)]


def _merge_parts(parts):
    if len(parts) == 1:
        return parts[0]
    return {k: np.concatenate([p[k] for p in parts], axis=0)
            for k in parts[0]}


def _unit_owned_by(shard_map, unit, me):
    from paddle_tpu.distributed import membership as mb
    kind, name, vsh = mb.parse_unit(unit)
    if kind == "d":
        return shard_map.get("dense", {}).get(name) == me
    owners = (shard_map.get("sparse") or {}).get(name, {})
    return owners.get(str(vsh)) == me


# Epoch-fenced data kinds → their legacy twins. The _E variants carry
# the client's committed fleet epoch as field 0; the server strips it,
# fences, and dispatches the legacy arm (docs/ELASTIC_TRAINING.md
# "Resizing the pserver fleet").
_EPOCH_KINDS = {
    wire.PUSH_GRAD_E: wire.PUSH_GRAD,
    wire.PULL_PARAM_E: wire.PULL_PARAM,
    wire.PULL_SPARSE_E: wire.PULL_SPARSE,
    wire.PUSH_SPARSE_E: wire.PUSH_SPARSE,
}


class ParameterServer(_SnapshotLoop):
    """listen_and_serv parity: hosts a set of dense vars + sparse tables,
    applies optimizer updates on grad fan-in, serves pulls/barriers/
    checkpoint-notify over TCP."""

    def __init__(self, endpoint, num_trainers=1, sync_mode=True):
        self.host, port = endpoint.rsplit(":", 1)
        self.port = int(port)
        self.num_trainers = num_trainers
        self.sync_mode = sync_mode
        self.incarnation = _new_incarnation()
        self._save_lock = threading.Lock()
        self.dense = {}
        self.sparse = {}
        self._barrier_lock = threading.Condition()
        self._barrier_waiting = {}    # tag -> set(trainer ids)
        self._barrier_gen = {}
        self._server = None
        self._thread = None
        # elastic fleet membership (docs/ELASTIC_TRAINING.md "Resizing
        # the pserver fleet"): the committed epoch + shard map this
        # server fences data frames against (None = the implicit
        # epoch-0 static placement — no fencing, today's behavior),
        # hosting recipes for units migrated IN, the shadow-staging
        # dir, and the freeze gate migration holds over moving units
        self.epoch = 0
        self.shard_map = None
        self.recipes = {}
        self.state_dir = None
        self._mig_cv = threading.Condition()
        self._frozen = set()          # unit keys mid-migration
        self._busy = {}               # unit key -> in-flight op count
        self._staged = {}             # epoch -> {unit: entry}
        # retry dedup for mutating requests (grpc retry-idempotence
        # role): per-client bounded LRU of seq -> cached reply, plus an
        # in-flight set so a retry that races the original request
        # waits for it instead of re-applying. Scoped PER CLIENT — a
        # single global LRU would let one chatty client evict another
        # client's in-retry entry and silently re-apply its mutation.
        # The per-client window must cover a multi-threaded client's
        # worst case: one thread backing off through retries while the
        # Communicator thread streams mutations on the shared seq
        # counter — hence 1024, not a handful.
        self._dedup = collections.OrderedDict()   # client -> LRU
        self._dedup_clients_cap = 256
        self._dedup_per_client_cap = 1024
        self._inflight = set()
        self._dedup_cv = threading.Condition()
        # highest seq handled per client — outlives the reply LRU (own
        # larger cap, FIFO), so a retry whose cached reply was evicted
        # is detectable: its seq is well below last_seen yet absent
        # from the LRU. Such a frame is re-applied (we can't answer
        # from cache) but counted + logged so silent double-apply is at
        # least observable. The tolerance below keeps legitimately
        # out-of-order first-time frames (threads sharing one seq
        # counter over separate connections) from tripping it.
        self._dedup_last_seen = collections.OrderedDict()
        self._dedup_last_seen_cap = 16384
        self._replay_seq_tolerance = 8
        self.possible_replays = 0

    # -- hosting -----------------------------------------------------------
    def host_dense(self, name, value, optimizer=None, regularizer=None,
                   param_lr=1.0):
        self.dense[name] = _DenseVar(value, optimizer, regularizer,
                                     param_lr)

    def host_sparse(self, name, dim, initializer=None, seed=0, lr=1.0,
                    optimizer="sgd"):
        self.sparse[name] = _SparseTable(dim, initializer, seed, lr,
                                         optimizer)

    # -- elastic-membership fencing (docs/ELASTIC_TRAINING.md) --------------
    def _map_json(self):
        return json.dumps(self.shard_map or {})

    def _fence_reply(self):
        """WRONG_EPOCH carrying the committed epoch + map: the client
        adopts the newer map and re-routes without a second probe.
        Nothing was applied when this reply is sent."""
        return (wire.WRONG_EPOCH, (int(self.epoch), self._map_json()))

    def _owns_dense(self, name):
        if self.shard_map is None:
            return True
        return self.shard_map.get("dense", {}).get(
            name, self.endpoint) == self.endpoint

    def _owns_sparse(self, name, ids):
        if self.shard_map is None:
            return True
        owners = (self.shard_map.get("sparse") or {}).get(name)
        if owners is None:
            return True           # table outside the map: static route
        from paddle_tpu.distributed import membership as mb
        me = self.endpoint
        return all(owners.get(str(int(v))) == me
                   for v in np.unique(mb.vshard_of(ids)))

    def _sparse_units(self, name, ids):
        from paddle_tpu.distributed import membership as mb
        return {mb.sparse_unit(name, int(v))
                for v in np.unique(mb.vshard_of(ids))}

    def _admit(self, epoch, units, owned, timeout=150.0):
        """Epoch fence + ownership check + migration-freeze gate for
        one data request. Returns a WRONG_EPOCH reply to send, or None
        after marking the units busy (caller must _release(units)).
        Requests touching a frozen (mid-migration) unit wait here and
        re-evaluate the fence — after a commit they bounce with
        WRONG_EPOCH instead of mutating a retired shard."""
        deadline = time.monotonic() + timeout
        while True:
            if epoch is not None and int(epoch) != self.epoch:
                return self._fence_reply()
            if self.shard_map is not None and not owned():
                return self._fence_reply()
            with self._mig_cv:
                if not (self._frozen & units):
                    for u in units:
                        self._busy[u] = self._busy.get(u, 0) + 1
                    return None
                left = deadline - time.monotonic()
                enforce(left > 0, "request blocked on a migration "
                                  "freeze that never released")
                self._mig_cv.wait(timeout=min(left, 1.0))

    def _release(self, units):
        with self._mig_cv:
            for u in units:
                n = self._busy.get(u, 0) - 1
                if n <= 0:
                    self._busy.pop(u, None)
                else:
                    self._busy[u] = n
            self._mig_cv.notify_all()

    # -- request handling (request_handler_impl.cc parity) -----------------
    def _handle(self, kind, fields):
        """Dispatch one decoded request; returns (resp_kind, fields)."""
        epoch = None
        legacy = _EPOCH_KINDS.get(kind)
        if legacy is not None:
            epoch, fields = int(fields[0]), fields[1:]
            kind = legacy
        if kind == wire.PUSH_GRAD:
            name, trainer_id, grad = fields
            units = {"d/" + name}
            gate = self._admit(epoch, units,
                               lambda: self._owns_dense(name))
            if gate is not None:
                return gate
            try:
                v = self.dense[name]
                if self.sync_mode:
                    v.push_sync(int(trainer_id), grad,
                                self.num_trainers)
                else:
                    v.push_async(grad)
            except _UnitRetired:
                return self._fence_reply()
            finally:
                self._release(units)
            return (wire.OK, ())
        if kind == wire.PULL_PARAM:
            name, min_round = fields
            if not self.sync_mode:
                min_round = 0
            # fence + ownership only — no freeze gate: a sync pull may
            # legitimately wait minutes for a round fan-in, and holding
            # the busy count through that wait would deadlock the
            # migration drain against the pushes it gates
            if epoch is not None and int(epoch) != self.epoch:
                return self._fence_reply()
            if self.shard_map is not None and \
                    not self._owns_dense(name):
                return self._fence_reply()
            try:
                return (wire.OK_ARR,
                        (self.dense[name].pull(int(min_round)),))
            except _UnitRetired:
                return self._fence_reply()
        if kind == wire.PULL_SPARSE:
            # the python-store pull MATERIALIZES missing rows — it
            # mutates, so it takes the freeze gate like a push
            name, ids = fields
            units = self._sparse_units(name, ids)
            gate = self._admit(epoch, units,
                               lambda: self._owns_sparse(name, ids))
            if gate is not None:
                return gate
            try:
                return (wire.OK_ARR, (self.sparse[name].pull(ids),))
            finally:
                self._release(units)
        if kind == wire.PUSH_SPARSE:
            name, ids, grads, lr = fields
            units = self._sparse_units(name, ids)
            gate = self._admit(epoch, units,
                               lambda: self._owns_sparse(name, ids))
            if gate is not None:
                return gate
            try:
                self.sparse[name].push(ids, grads, lr)
            finally:
                self._release(units)
            return (wire.OK, ())
        if kind == wire.BARRIER:
            tag, trainer_id = fields
            trainer_id = int(trainer_id)
            with self._barrier_lock:
                gen = self._barrier_gen.setdefault(tag, 0)
                # set-based fan-in: a retried barrier frame from the
                # same trainer is idempotent
                waiting = self._barrier_waiting.setdefault(tag, set())
                waiting.add(trainer_id)
                if len(waiting) >= self.num_trainers:
                    waiting.clear()
                    self._barrier_gen[tag] = gen + 1
                    self._barrier_lock.notify_all()
                else:
                    ok = self._barrier_lock.wait_for(
                        lambda: self._barrier_gen[tag] > gen, timeout=120.0)
                    enforce(ok, f"barrier {tag!r} timed out")
            return (wire.OK, ())
        if kind == wire.CHECKPOINT_NOTIFY:
            (dirname,) = fields
            self.save(dirname)
            return (wire.OK, ())
        if kind == wire.SHRINK_TABLE:
            name, max_age = fields
            removed = self.sparse[name].shrink(int(max_age))
            return (wire.OK_ARR,
                    (np.asarray([removed], np.int64),))
        if kind == wire.LIST_VARS:
            return (wire.OK_NAMES, ("\n".join(sorted(self.dense)),
                                    "\n".join(sorted(self.sparse))))
        if kind == wire.SERVER_INFO:
            # the failover probe: [incarnation, min dense round] — a
            # reconnecting client compares the token against the one it
            # last saw and, on a change, re-establishes its sync-round
            # expectations at the round the reborn server can serve
            return (wire.OK_ARR,
                    (np.asarray([self.incarnation, self._min_round()],
                                np.int64),))
        if kind == wire.STOP:
            def stop_after_grace():
                # only a multi-trainer job has the in-flight-reply
                # race the grace exists for
                if self.num_trainers > 1:
                    time.sleep(_stop_grace_seconds())
                self.stop()

            threading.Thread(target=stop_after_grace,
                             daemon=True).start()
            return (wire.OK, ())
        if kind == wire.MIGRATE_PLAN:
            return self._migrate_source(json.loads(fields[0]))
        if kind == wire.MIGRATE_BEGIN:
            return self._migrate_begin(json.loads(fields[0]))
        if kind == wire.MIGRATE_CHUNK:
            meta, blob, crc = fields
            return self._migrate_chunk(json.loads(meta), blob,
                                       int(crc))
        if kind == wire.MIGRATE_END:
            return self._migrate_end(json.loads(fields[0]))
        if kind == wire.MIGRATE_COMMIT:
            spec = json.loads(fields[0])
            return self._migrate_commit(int(spec["epoch"]),
                                        spec["map"])
        if kind == wire.MIGRATE_ABORT:
            return self._migrate_abort(
                int(json.loads(fields[0])["epoch"]))
        if kind == wire.EPOCH_MAP:
            return (wire.OK_JSON,
                    (json.dumps({"epoch": int(self.epoch),
                                 "map": self.shard_map}),))
        return (wire.ERR, (f"unhandled request kind {kind}",))

    # -- two-phase migration: source side ----------------------------------
    def _migrate_source(self, plan):
        """MIGRATE_PLAN from the coordinator: freeze the moving units,
        drain in-flight writes, stream every unit to its target, and
        stay frozen until COMMIT or ABORT resolves the epoch. Any
        failure unfreezes and replies ERR — the ERR reply is the
        coordinator's abort trigger, and once unfrozen this (still
        authoritative) server keeps applying writes at the old epoch."""
        _migrate_fault_point("plan")
        epoch = int(plan["epoch"])
        units = [(u["unit"], u["to"]) for u in plan["units"]]
        names = {u for u, _ in units}
        with self._mig_cv:
            self._frozen |= names
            ok = self._mig_cv.wait_for(
                lambda: not any(self._busy.get(u) for u in names),
                timeout=30.0)
            if not ok:
                self._frozen -= names
                self._mig_cv.notify_all()
                return (wire.ERR,
                        ("migration freeze drain timed out",))
        by_target = {}
        for u, to in units:
            by_target.setdefault(to, []).append(u)
        rows = 0
        try:
            for to in sorted(by_target):
                rows += self._stream_units(to, epoch, by_target[to])
        except Exception as e:                      # noqa: BLE001
            with self._mig_cv:
                self._frozen -= names
                self._mig_cv.notify_all()
            return (wire.ERR,
                    (f"migration stream failed: "
                     f"{type(e).__name__}: {e}",))
        return (wire.OK_ARR, (np.asarray([rows], np.int64),))

    def _stream_units(self, to, epoch, units):
        from paddle_tpu.distributed import membership as mb
        mb._rpc(to, wire.MIGRATE_BEGIN,
                (json.dumps({"epoch": epoch, "from": self.endpoint,
                             "units": list(units)}),))
        rows = 0
        for unit in sorted(units):
            _migrate_fault_point("chunk")
            kind, name, vsh = mb.parse_unit(unit)
            if kind == "d":
                chunks = [self._export_dense_unit(name)]
                rows += 1
            else:
                ids, vals, accum = self.sparse[name].snapshot()
                sel = mb.vshard_of(ids) == vsh
                ids, vals = ids[sel], vals[sel]
                accum = accum[sel] if accum is not None else \
                    np.zeros_like(vals)
                rows += int(ids.size)
                chunks = _row_chunks(ids, vals, accum)
            last = len(chunks) - 1
            for i, arrays in enumerate(chunks):
                blob, crc = mb.pack_arrays(arrays)
                mb._rpc(to, wire.MIGRATE_CHUNK,
                        (json.dumps({"unit": unit, "epoch": epoch,
                                     "seq": i,
                                     "last": i == last}),
                         blob, crc))
        mb._rpc(to, wire.MIGRATE_END,
                (json.dumps({"epoch": epoch, "from": self.endpoint,
                             "units": list(units)}),))
        return rows

    def _export_dense_unit(self, name):
        """Copy a dense var for the wire — including the mid-round
        fan-in (accum + pushed set): with multiple trainers a round may
        be half-collected at freeze time, and the target must resume
        the fan-in exactly where the source stopped or the round
        double-counts the already-pushed trainers."""
        v = self.dense[name]
        with v.cv:
            out = {"value": np.array(v.value, copy=True),
                   "round": np.asarray([v.round], np.int64),
                   "step": np.asarray([v.step_count], np.int64),
                   "pushed": np.asarray(sorted(v.pushed), np.int64)}
            if v.accum is not None:
                out["accum"] = np.array(v.accum, copy=True)
            if v.slots:
                for k, s in v.slots.items():
                    out["slot/" + k] = np.array(s, copy=True)
        return out

    # -- two-phase migration: target side ----------------------------------
    def _migrate_begin(self, spec):
        epoch = int(spec["epoch"])
        for unit in spec["units"]:
            from paddle_tpu.distributed import membership as mb
            kind, name, _ = mb.parse_unit(unit)
            hosted = name in (self.dense if kind == "d"
                              else self.sparse)
            if not hosted and name not in self.recipes:
                return (wire.ERR,
                        (f"no hosting recipe for migrated "
                         f"unit {unit!r}",))
        with self._mig_cv:
            stage = self._staged.setdefault(epoch, {})
            for unit in spec["units"]:
                stage[unit] = {"parts": [],
                               "from": spec.get("from")}
        return (wire.OK, ())

    def _migrate_chunk(self, meta, blob, crc):
        from paddle_tpu.distributed import membership as mb
        import zlib
        if zlib.crc32(blob.tobytes()) & 0xFFFFFFFF != crc:
            return (wire.ERR,
                    (f"migration chunk CRC mismatch for "
                     f"{meta.get('unit')!r}",))
        epoch, unit = int(meta["epoch"]), meta["unit"]
        with self._mig_cv:
            ent = self._staged.get(epoch, {}).get(unit)
        if ent is None:
            return (wire.ERR,
                    (f"chunk for unstaged unit {unit!r}",))
        ent["parts"].append(mb.unpack_blob(blob))
        return (wire.OK, ())

    def _migrate_end(self, spec):
        """Source finished streaming: merge the chunks and publish each
        unit as a durable, CRC-manifested shadow file. The shadow is
        what survives a target crash between staging and commit — the
        warm-boot reconcile adopts it if the epoch file says we won."""
        from paddle_tpu.distributed import membership as mb
        from paddle_tpu import io_checkpoint as ioc
        if not self.state_dir:
            return (wire.ERR,
                    ("target has no state_dir for shadow staging",))
        epoch = int(spec["epoch"])
        tag = mb.tag_of_ep(self.endpoint)
        staged_rows = 0
        for unit in spec["units"]:
            with self._mig_cv:
                ent = self._staged.get(epoch, {}).get(unit)
            if ent is None:
                return (wire.ERR,
                        (f"END for unstaged unit {unit!r}",))
            arrays = _merge_parts(ent["parts"])
            ent["arrays"] = arrays
            path = mb.shadow_path(self.state_dir, tag, epoch, unit)
            ioc.publish_npz(path, arrays,
                            {"kind": "pserver_shadow",
                             "endpoint": self.endpoint,
                             "epoch": epoch, "unit": unit})
            _migrate_fault_point("staged", path)
            ids = arrays.get("ids")
            staged_rows += int(ids.size) if ids is not None else 1
        return (wire.OK_ARR,
                (np.asarray([staged_rows], np.int64),))

    # -- two-phase migration: resolution -----------------------------------
    def _migrate_commit(self, epoch, new_map):
        """Coordinator published fleet_epoch.json (the commit point)
        and is now telling everyone. Idempotent: a retried COMMIT after
        we already moved to `epoch` is a no-op ack. Adopt what we
        staged, retire what we lost, serve the new epoch."""
        _migrate_fault_point("commit")
        if self.epoch >= epoch:
            return (wire.OK_ARR,
                    (np.asarray([self.epoch], np.int64),))
        with self._mig_cv:
            staged = self._staged.pop(epoch, {})
        from paddle_tpu.distributed import membership as mb
        adopted = 0
        for unit in sorted(staged):
            if not _unit_owned_by(new_map, unit, self.endpoint):
                continue
            ent = staged[unit]
            arrays = ent.get("arrays")
            if arrays is None:
                # crashed-and-respawned between END and COMMIT: the
                # in-memory parts are gone but the shadow survived
                from paddle_tpu import io_checkpoint as ioc
                tag = mb.tag_of_ep(self.endpoint)
                path = mb.shadow_path(self.state_dir, tag, epoch,
                                      unit)
                try:
                    _, arrays = ioc.verify_npz(path)
                except Exception as e:              # noqa: BLE001
                    return (wire.ERR,
                            (f"staged shadow for {unit!r} "
                             f"unreadable: {e}",))
            adopted += self._adopt_unit(unit, arrays)
        if adopted:
            _m_migrated.inc(adopted)
        self._retire_units(new_map)
        self.epoch = int(epoch)
        self.shard_map = new_map
        _m_epoch.set(self.epoch)
        with self._mig_cv:
            self._frozen.clear()
            for e in [e for e in self._staged if e <= epoch]:
                self._staged.pop(e, None)
            self._mig_cv.notify_all()
        snap_dir = getattr(self, "_snap_dir", None)
        saved = True
        if snap_dir:
            try:
                self.save(snap_dir)
            except Exception as e:                  # noqa: BLE001
                # keep the staged shadows: until a snapshot holding
                # the adopted rows lands, they are the only durable
                # copy — a crash now must find them at respawn
                saved = False
                _ps_log(f"post-commit snapshot failed: {e}")
        if saved:
            self._sweep_my_shadows(max_epoch=epoch)
        _ps_log(f"committed fleet epoch {epoch} "
                f"(adopted {adopted} rows)")
        return (wire.OK_ARR,
                (np.asarray([self.epoch], np.int64),))

    def _migrate_abort(self, epoch):
        """Coordinator gave up on `epoch` before the commit point.
        Stale aborts (epoch already committed) must not act — the
        coordinator only aborts epochs it never published."""
        if epoch <= self.epoch:
            return (wire.OK_ARR,
                    (np.asarray([self.epoch], np.int64),))
        with self._mig_cv:
            self._staged.pop(epoch, None)
            self._frozen.clear()
            self._mig_cv.notify_all()
        self._sweep_my_shadows(min_epoch=epoch)
        _ps_log(f"aborted migration toward epoch {epoch}; "
                f"serving epoch {self.epoch}")
        return (wire.OK_ARR,
                (np.asarray([self.epoch], np.int64),))

    def _sweep_my_shadows(self, min_epoch=None, max_epoch=None):
        if not self.state_dir:
            return
        from paddle_tpu.distributed import membership as mb
        tag = mb.tag_of_ep(self.endpoint)
        for path, _, ep, _ in mb.list_shadows(self.state_dir,
                                              tag=tag):
            if min_epoch is not None and ep < min_epoch:
                continue
            if max_epoch is not None and ep > max_epoch:
                continue
            try:
                os.remove(path)
            except OSError:
                pass

    def _adopt_unit(self, unit, arrays):
        """Install one migrated unit, hosting it from the recipe if it
        is not already resident. Returns the row count adopted."""
        from paddle_tpu.distributed import membership as mb
        kind, name, vsh = mb.parse_unit(unit)
        if kind == "d":
            if name not in self.dense:
                rec = self.recipes.get(name, {})
                self.host_dense(
                    name, np.zeros_like(arrays["value"]),
                    optimizer=rec.get("optimizer"),
                    regularizer=rec.get("regularizer"),
                    param_lr=rec.get("param_lr", 1.0))
            v = self.dense[name]
            with v.cv:
                v.value = np.ascontiguousarray(arrays["value"])
                v.round = int(arrays["round"][0])
                v.step_count = int(arrays["step"][0])
                slots = {k[len("slot/"):]:
                         np.ascontiguousarray(a, dtype=np.float32)
                         for k, a in arrays.items()
                         if k.startswith("slot/")}
                v.slots = slots or None
                v.accum = (np.ascontiguousarray(arrays["accum"])
                           if "accum" in arrays else None)
                v.pushed = set(int(t) for t
                               in arrays.get("pushed", []))
                v.evicted = False
                v.cv.notify_all()
            return 1
        if name not in self.sparse:
            rec = self.recipes.get(name, {})
            self.host_sparse(name, int(rec["dim"]),
                             initializer=rec.get("initializer"),
                             seed=rec.get("seed", 0),
                             lr=rec.get("lr", 1.0),
                             optimizer=rec.get("optimizer", "sgd"))
        tbl = self.sparse[name]
        ids_in = np.asarray(arrays["ids"], np.int64)
        rows_in = np.asarray(arrays["rows"], np.float32)
        acc_in = np.asarray(arrays["accum"], np.float32)
        ids0, rows0, acc0 = tbl.snapshot()
        if acc0 is None:
            acc0 = np.zeros_like(rows0)
        keep = mb.vshard_of(ids0) != vsh if ids0.size else \
            np.zeros(0, bool)
        tbl.restore(np.concatenate([ids0[keep], ids_in]),
                    np.concatenate([rows0[keep], rows_in])
                    if rows0.size or rows_in.size else rows_in,
                    np.concatenate([acc0[keep], acc_in])
                    if acc0.size or acc_in.size else acc_in)
        return int(ids_in.size)

    def _retire_units(self, new_map):
        """Drop everything the new map assigns elsewhere. Dense vars
        are evicted (wakes blocked pullers/pushers into _UnitRetired →
        WRONG_EPOCH); sparse tables stay hosted but shed the vshards
        they lost — a table with zero vshards still answers BEGIN for
        a later grow."""
        from paddle_tpu.distributed import membership as mb
        me = self.endpoint
        dense_map = new_map.get("dense", {})
        for name in list(self.dense):
            if dense_map.get(name, me) != me:
                v = self.dense.pop(name)
                with v.cv:
                    v.evicted = True
                    v.cv.notify_all()
        sparse_map = new_map.get("sparse", {})
        for name, tbl in self.sparse.items():
            owners = sparse_map.get(name)
            if owners is None:
                continue
            mine = {int(v) for v, ep in owners.items() if ep == me}
            ids, rows, acc = tbl.snapshot()
            if not ids.size:
                continue
            keep = np.isin(mb.vshard_of(ids),
                           np.asarray(sorted(mine), np.int64))
            if keep.all():
                continue
            tbl.restore(ids[keep], rows[keep],
                        acc[keep] if acc is not None else None)

    def _handle_frame(self, kind, client_id, seq, fields):
        """Dedup wrapper: retried mutating frames (same client, same
        seq) are answered from the cached reply, never re-applied; a
        retry racing the still-running original waits for it."""
        if kind not in wire.MUTATING or not client_id:
            return self._handle(kind, fields)
        key = (client_id, seq)

        def cached():
            lru = self._dedup.get(client_id)
            if lru is not None and seq in lru:
                lru.move_to_end(seq)
                self._dedup.move_to_end(client_id)
                return lru[seq]
            return None

        with self._dedup_cv:
            while True:
                resp = cached()
                if resp is not None:
                    return resp
                if key not in self._inflight:
                    last = self._dedup_last_seen.get(client_id, -1)
                    if seq <= last - self._replay_seq_tolerance:
                        # known client, seq far behind its high-water
                        # mark, and no cached reply: this apply is a
                        # probable double-apply of a retry whose dedup
                        # entry was LRU-evicted.
                        self.possible_replays += 1
                        logging.getLogger("paddle_tpu.ps").warning(
                            "retry-dedup cache miss for %s seq=%d "
                            "(last_seen=%d): mutating frame will be "
                            "re-applied", client_id, seq, last)
                    self._inflight.add(key)
                    break
                ok = self._dedup_cv.wait_for(
                    lambda: cached() is not None
                    or key not in self._inflight, timeout=150.0)
                enforce(ok, f"duplicate frame {key} timed out waiting "
                            f"for the original")
        try:
            resp = self._handle(kind, fields)
            with self._dedup_cv:
                lru = self._dedup.get(client_id)
                if lru is None:
                    lru = self._dedup[client_id] = \
                        collections.OrderedDict()
                lru[seq] = resp
                if seq > self._dedup_last_seen.get(client_id, -1):
                    self._dedup_last_seen[client_id] = seq
                    self._dedup_last_seen.move_to_end(client_id)
                    while (len(self._dedup_last_seen)
                           > self._dedup_last_seen_cap):
                        self._dedup_last_seen.popitem(last=False)
                self._dedup.move_to_end(client_id)
                while len(lru) > self._dedup_per_client_cap:
                    lru.popitem(last=False)
                while len(self._dedup) > self._dedup_clients_cap:
                    self._dedup.popitem(last=False)
            return resp
        finally:
            with self._dedup_cv:
                self._inflight.discard(key)
                self._dedup_cv.notify_all()

    def _min_round(self):
        rounds = []
        for v in self.dense.values():
            with v.cv:
                rounds.append(int(v.round))
        return min(rounds) if rounds else 0

    # -- checkpoint (kCheckpointBlockId parity) ----------------------------
    def _dense_export(self):
        """(values, var_state, slots) — each var copied under its cv:
        the native step mutates slot buffers in place, and a mid-step
        serialization must not see a half-updated state. Per-var
        atomic; a sync round's partial fan-in (accum/pushed) is NOT
        snapshotted — after a restart the trainers re-push the round."""
        values, state, slots = {}, {}, {}
        for n, v in self.dense.items():
            with v.cv:
                values[n] = np.array(v.value, copy=True)
                state[n] = (int(v.round), int(v.step_count))
                if v.slots:
                    slots[n] = {k: np.array(s, copy=True)
                                for k, s in v.slots.items()}
        return values, state, slots

    def _dense_import(self, name, value, state, slots):
        v = self.dense.get(name)
        if v is None:
            # elastic warm boot: a var this server adopted via
            # migration is in the snapshot but not in the transpiled
            # program — re-host it from the recipe before restoring
            rec = self.recipes.get(name)
            if rec is None or rec.get("kind") != "dense":
                return
            self.host_dense(name, np.zeros_like(np.asarray(value)),
                            optimizer=rec.get("optimizer"),
                            regularizer=rec.get("regularizer"),
                            param_lr=rec.get("param_lr", 1.0))
            v = self.dense[name]
        with v.cv:
            v.value = np.asarray(value)
            if state is not None:
                v.round, v.step_count = state
            if slots:
                # contiguous float32: the native dense kernels hand
                # these buffers to C by pointer
                v.slots = {k: np.ascontiguousarray(a, np.float32)
                           for k, a in slots.items()}
            v.accum = None
            v.pushed.clear()
            v.cv.notify_all()

    def load(self, dirname):
        """Warm boot: restore the newest integrity-verified snapshot
        generation (walking back past corrupt ones). Returns the
        restored generation's meta, or None when nothing restorable
        exists."""
        def make_table(t):
            rec = self.recipes.get(t)
            if rec is None or rec.get("kind") != "sparse":
                return None
            self.host_sparse(t, int(rec["dim"]),
                             initializer=rec.get("initializer"),
                             seed=rec.get("seed", 0),
                             lr=rec.get("lr", 1.0),
                             optimizer=rec.get("optimizer", "sgd"))
            return self.sparse[t]
        return _ps_checkpoint_load(dirname, self.host, self.port,
                                   self._dense_import, self.sparse,
                                   make_table=make_table)

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        handle_frame = self._handle_frame

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                try:
                    while True:
                        # header and payload decode separately so a
                        # payload-malformed reply can still echo
                        # (cid, seq) — the client's stale-reply check
                        # would otherwise reject the typed error
                        try:
                            kind, cid, seq, n = wire.decode_header(
                                _recv_exact(self.request,
                                            wire.HEADER_SIZE))
                        except wire.WireError as e:
                            try:
                                _reply_frame(self.request, wire.ERR,
                                             (f"malformed frame: {e}",))
                            except OSError:
                                pass
                            return
                        try:
                            fields = wire.decode_payload(
                                kind, _recv_exact(self.request, n))
                        except wire.WireError as e:
                            # bytes were never evaluated; typed error,
                            # drop the connection
                            try:
                                _reply_frame(self.request, wire.ERR,
                                             (f"malformed frame: {e}",),
                                             cid, seq)
                            except OSError:
                                pass
                            return
                        try:
                            rk, rf = handle_frame(kind, cid, seq, fields)
                        except Exception as e:
                            rk, rf = wire.ERR, (f"{type(e).__name__}: "
                                                f"{e}",)
                        # echo (client_id, seq): the client rejects a
                        # reply whose seq does not match its request
                        # (a late reply to a timed-out call must never
                        # be consumed as the next call's answer).
                        # _reply_frame, not _send_frame: the module
                        # hook testing/faults' wire chaos patches
                        _reply_frame(self.request, rk, rf, cid, seq)
                except (ConnectionError, EOFError, OSError):
                    pass

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((self.host, self.port), Handler)
        if self.port == 0:
            self.port = self._server.server_address[1]
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    @property
    def endpoint(self):
        return f"{self.host}:{self.port}"

    def run(self):
        """Blocking serve (the listen_and_serv op's RunImpl): start if
        needed and wait until stop() — used by pserver processes."""
        if self._server is None:
            self.start()
        self._thread.join()

    def stop(self):
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None


class NativeUnsupported(Exception):
    """Hosted state not expressible by the C++ server (exotic
    optimizer/regularizer/schedule, non-f32 dtype, custom sparse
    initializer) — callers fall back to the Python ParameterServer."""


class _NativeDenseView:
    """Read-through view of a dense var hosted in the C++ server:
    `.value` and `.round` read the authoritative native state (the
    surface tests and checkpoints use)."""

    def __init__(self, server, name, shape, dtype):
        self._server = server
        self.name = name
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)

    @property
    def value(self):
        import ctypes
        srv = self._server
        out = np.empty(self.shape, np.float32)
        rc = srv._lib.pt_pss_dense_get(
            srv._h, self.name.encode(),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        enforce(rc == 0, f"no hosted dense var {self.name!r}")
        return out

    @value.setter
    def value(self, v):
        import ctypes
        srv = self._server
        v = np.ascontiguousarray(v, np.float32)
        rc = srv._lib.pt_pss_dense_set(
            srv._h, self.name.encode(),
            v.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), v.size)
        enforce(rc == 0, f"cannot set dense var {self.name!r} "
                         f"(size mismatch?)")

    @property
    def round(self):
        return int(self._server._lib.pt_pss_dense_round(
            self._server._h, self.name.encode()))


class NativeParameterServer(_SnapshotLoop):
    """The C++ control-plane transport (native/src/ps_server.cc):
    listen_and_serv parity with the SAME wire protocol and observable
    semantics as ParameterServer, but the accept loop, frame codec,
    request dispatch, dedup, and optimize kernels all run in C++ — a
    request never touches Python (SURVEY §5.8's hand-written-C++
    commitment; ref: operators/distributed/grpc/grpc_server.cc,
    request_handler_impl.cc). Checkpoint-notify calls back into Python
    to write the same npz artifacts as the Python server.

    Hosting raises NativeUnsupported for state the C++ side cannot
    express (callable LR schedules, exotic optimizers/regularizers,
    non-float32 params, custom sparse initializers); callers
    (make_parameter_server, PServerProgram.build_server) fall back to
    the Python server then."""

    _OPT_KINDS = {"none": 0, "sgd": 1, "momentum": 2, "adam": 3}

    def __init__(self, endpoint, num_trainers=1, sync_mode=True):
        from paddle_tpu import native
        self.host, port = endpoint.rsplit(":", 1)
        self.port = int(port)
        self.num_trainers = num_trainers
        self.sync_mode = sync_mode
        self._lib = native.get_lib()
        self._native_mod = native
        self._h = self._lib.pt_pss_new(
            self.host.encode(), self.port, num_trainers,
            1 if sync_mode else 0, wire.max_message_bytes())
        enforce(bool(self._h), "pt_pss_new failed")
        self._lib.pt_pss_set_stop_grace_ms(
            self._h, int(_stop_grace_seconds() * 1000))
        self.dense = {}            # name -> _NativeDenseView
        self.sparse = {}           # name -> NativeSparseTable view
        self._started = False
        self._stopped = False
        # the ctypes callback object must outlive the server
        self._ckpt_cb = native.PS_CKPT_CB(self._on_checkpoint)
        self._lib.pt_pss_set_checkpoint_cb(self._h, self._ckpt_cb)
        self.incarnation = _new_incarnation()
        self._lib.pt_pss_set_incarnation(self._h, self.incarnation)
        self._save_lock = threading.Lock()

    # -- expressibility ---------------------------------------------------
    @staticmethod
    def _opt_config(optimizer, regularizer):
        """(kind, lr, mu_or_b1, b2, eps, nesterov, decay, coeff) or
        raises NativeUnsupported. (param_lr is NOT folded in here — it
        passes to the C++ side separately and scales lr per step.)"""
        from paddle_tpu import optimizer as po
        if optimizer is None:
            return (0, 0.0, 0.0, 0.0, 0.0, 0, 0, 0.0)
        if callable(optimizer.learning_rate):
            raise NativeUnsupported("callable LR schedule")
        lr = float(optimizer.learning_rate)
        # exact type, not isinstance: subclasses define different rules
        if type(optimizer) is po.SGDOptimizer:
            cfg = (1, lr, 0.0, 0.0, 0.0, 0)
        elif type(optimizer) is po.MomentumOptimizer:
            cfg = (2, lr, float(optimizer.momentum), 0.0, 0.0,
                   int(bool(getattr(optimizer, "use_nesterov", False))))
        elif type(optimizer) is po.AdamOptimizer:
            cfg = (3, lr, float(optimizer.beta1), float(optimizer.beta2),
                   float(optimizer.epsilon), 0)
        else:
            raise NativeUnsupported(
                f"optimizer {type(optimizer).__name__}")
        reg = regularizer or optimizer.regularization
        if reg is None:
            decay = (0, 0.0)
        else:
            from paddle_tpu.regularizer import (L1DecayRegularizer,
                                                L2DecayRegularizer)
            if type(reg) is L2DecayRegularizer:
                decay = (1, float(reg.coeff))
            elif type(reg) is L1DecayRegularizer:
                decay = (2, float(reg.coeff))
            else:
                raise NativeUnsupported(
                    f"regularizer {type(reg).__name__}")
        return cfg + decay

    # -- hosting ----------------------------------------------------------
    def host_dense(self, name, value, optimizer=None, regularizer=None,
                   param_lr=1.0):
        import ctypes
        enforce(not self._started, "host_dense before start()")
        value = np.asarray(value)
        if value.dtype != np.float32:
            raise NativeUnsupported(f"dtype {value.dtype}")
        kind, lr, b1, b2, eps, nesterov, decay, coeff = \
            self._opt_config(optimizer, regularizer)
        v = np.ascontiguousarray(value, np.float32)
        dims = np.asarray(v.shape or (1,), np.uint32)
        rc = self._lib.pt_pss_host_dense(
            self._h, name.encode(),
            v.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            dims.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
            len(dims), kind, lr, b1, b2, eps, nesterov, decay, coeff,
            float(param_lr))
        enforce(rc == 0, "pt_pss_host_dense failed")
        self.dense[name] = _NativeDenseView(self, name,
                                            v.shape or (1,), v.dtype)

    def host_sparse(self, name, dim, initializer=None, seed=0, lr=1.0,
                    optimizer="sgd"):
        if initializer is not None:
            raise NativeUnsupported("custom sparse initializer")
        enforce(not self._started, "host_sparse before start()")
        enforce(optimizer in ("sgd", "adagrad"),
                f"sparse optimizer must be sgd|adagrad, got {optimizer!r}")
        rc = self._lib.pt_pss_host_sparse(
            self._h, name.encode(), int(dim),
            {"sgd": 0, "adagrad": 1}[optimizer], float(lr), 1e-6,
            int(seed) & 0xFFFFFFFFFFFFFFFF)
        enforce(rc == 0, "pt_pss_host_sparse failed")
        handle = self._lib.pt_pss_sparse_table(self._h, name.encode())
        self.sparse[name] = self._native_mod.NativeSparseTable \
            .from_handle(handle, dim, owner=self)

    # -- checkpoint (same artifacts as ParameterServer.save/load) ---------
    #: Python slot name -> native slot selector (ps_server.cc:
    #: pt_pss_dense_set_slot takes it directly; pt_pss_dense_export
    #: reports presence as the bitmask ``1 << which``). The artifact
    #: contract speaks the Python names so cross-transport restore
    #: works either direction.
    _SLOT_WHICH = {"velocity": 0, "moment1": 1, "moment2": 2}

    def _on_checkpoint(self, dirname):
        try:
            self.save(os.fsdecode(dirname))
        except Exception:
            logging.getLogger("paddle_tpu.ps").exception(
                "checkpoint-notify save failed")

    def _dense_export(self):
        import ctypes
        fp = ctypes.POINTER(ctypes.c_float)
        values, state, slots = {}, {}, {}
        for n, view in self.dense.items():
            count = int(np.prod(view.shape or (1,), dtype=np.int64))
            # ONE native lock acquisition per var (pt_pss_dense_export)
            # copies value + round/step + every materialized slot
            # together: reading them through separate getters would let
            # an optimizer step land in between and publish round R+1
            # stamped onto round-R parameters — a torn snapshot whose
            # lost update no staleness accounting would ever see (the
            # Python transport's export holds the var cv the same way)
            val = np.empty(count, np.float32)
            bufs = {k: np.empty(count, np.float32)
                    for k in self._SLOT_WHICH}
            rnd = ctypes.c_uint64()
            stp = ctypes.c_long()
            have = ctypes.c_int()
            rc = self._lib.pt_pss_dense_export(
                self._h, n.encode(), val.ctypes.data_as(fp),
                ctypes.byref(rnd), ctypes.byref(stp),
                bufs["velocity"].ctypes.data_as(fp),
                bufs["moment1"].ctypes.data_as(fp),
                bufs["moment2"].ctypes.data_as(fp),
                ctypes.byref(have))
            enforce(rc == 0, f"no hosted dense var {n!r}")
            values[n] = val.reshape(view.shape)
            state[n] = (int(rnd.value), int(stp.value))
            sl = {k: bufs[k].reshape(view.shape)
                  for k, which in self._SLOT_WHICH.items()
                  if have.value & (1 << which)}
            if sl:
                slots[n] = sl
        return values, state, slots

    def _dense_import(self, name, value, state, slots):
        import ctypes
        fp = ctypes.POINTER(ctypes.c_float)
        view = self.dense.get(name)
        if view is None:
            return
        view.value = value
        if state is not None:
            self._lib.pt_pss_dense_set_state(
                self._h, name.encode(), int(state[0]), int(state[1]))
        for k, a in (slots or {}).items():
            which = self._SLOT_WHICH.get(k)
            if which is None:
                continue
            a = np.ascontiguousarray(a, np.float32).ravel()
            self._lib.pt_pss_dense_set_slot(
                self._h, name.encode(), which,
                a.ctypes.data_as(fp), a.size)

    def load(self, dirname):
        """Warm boot (see ParameterServer.load): returns the restored
        generation's meta or None."""
        return _ps_checkpoint_load(dirname, self.host, self.port,
                                   self._dense_import, self.sparse)

    # -- observability ----------------------------------------------------
    @property
    def possible_replays(self):
        return int(self._lib.pt_pss_possible_replays(self._h))

    # -- lifecycle --------------------------------------------------------
    def start(self):
        enforce(not self._started, "already started")
        port = self._lib.pt_pss_start(self._h)
        enforce(port > 0, f"native PS server failed to start: "
                          f"{self._lib.pt_pss_error(self._h).decode()}")
        self.port = port
        self._started = True
        return self

    @property
    def endpoint(self):
        return f"{self.host}:{self.port}"

    def run(self):
        """Blocking serve (listen_and_serv RunImpl): waits inside the
        C++ server until a STOP frame or stop() — ctypes releases the
        GIL for the duration."""
        if not self._started:
            self.start()
        self._lib.pt_pss_join(self._h)
        self.stop()

    def stop(self):
        if self._started and not self._stopped:
            self._lib.pt_pss_stop(self._h)
            self._stopped = True

    def __del__(self):
        try:
            self.stop()
            self._lib.pt_pss_free(self._h)
        except Exception:
            pass


def _is_missing_toolchain(e):
    """True for the RuntimeError the lazy native build raises when no
    C++ toolchain is present (native/_build) — the one native-transport
    failure that auto mode swallows silently by design. Shared by
    make_parameter_server and PServerProgram.build_server so the two
    fallback sites can't drift."""
    return isinstance(e, RuntimeError) and "native build failed" in str(e)


def make_parameter_server(endpoint, num_trainers=1, sync_mode=True,
                          transport=None):
    """Factory honoring FLAGS_ps_transport: the C++ server when the
    toolchain is present (hosting may still fall back — see
    PServerProgram.build_server), the Python server otherwise."""
    transport = transport or get_flag("ps_transport")
    enforce(transport in ("auto", "native", "python"),
            f"FLAGS_ps_transport must be auto|native|python, "
            f"got {transport!r}")
    if transport == "python":
        return ParameterServer(endpoint, num_trainers, sync_mode)
    try:
        return NativeParameterServer(endpoint, num_trainers, sync_mode)
    except Exception as e:
        if transport == "native":
            raise
        # auto: a missing toolchain falls back silently by design; any
        # OTHER failure is a native-path bug that must not hide behind
        # the ~2x-slower Python transport unannounced
        if not isinstance(e, NativeUnsupported) \
                and not _is_missing_toolchain(e):
            logging.getLogger("paddle_tpu.ps").warning(
                "native PS transport failed unexpectedly (%s: %s) — "
                "falling back to the Python server",
                type(e).__name__, e)
        return ParameterServer(endpoint, num_trainers, sync_mode)


class _Rerouted(Exception):
    """A call was fenced (WRONG_EPOCH) or its endpoint retired: the
    client adopted a newer shard map and the caller must recompute the
    route and re-send. The fenced server applied NOTHING, so the
    re-send (with a fresh seq) stays exactly-once."""


class PSClient:
    """RPCClient parity (rpc_client.h:33): persistent connections to every
    pserver, var→endpoint routing, send/get/prefetch/barrier/checkpoint.
    Connection failures retry with exponential backoff (grpc_client.cc
    retry path); retried mutating frames carry the same (client_id, seq)
    so the server dedups instead of re-applying.

    Pserver-restart awareness (docs/ELASTIC_TRAINING.md "Pserver
    failover"): a connection-REFUSED/RESET failure is pserver downtime
    under supervised failover, retried against a wall-clock budget
    (``PT_PS_RECONNECT_SECS``, default 60 — sized for respawn backoff
    plus a worker-process warm boot) rather than the fixed attempt
    count transient blips get. Every fresh connection probes
    ``SERVER_INFO``; a changed incarnation token means the server
    restarted from its last snapshot, and the next sync-mode pull
    re-establishes its round expectation at the server's round —
    counting the lost rounds in ``ps_stale_rounds_total`` — instead of
    blocking 120 s for a round the reborn server will never reach."""

    MAX_RETRIES = 5
    BACKOFF = 0.05          # seconds, doubles per attempt (cap 2 s)

    def __init__(self, endpoints, var_ep=None, trainer_id=0,
                 timeout=150.0):
        self.endpoints = list(endpoints)
        self.var_ep = dict(var_ep or {})
        self.trainer_id = trainer_id
        self.client_id = int.from_bytes(os.urandom(8), "little") or 1
        # per-connection reply timeout; the 150 s default stays above
        # the server-side wait timeouts (120 s) so the server's own
        # EnforceNotMet surfaces as a typed error response before the
        # transport gives up. Chaos tests lower it.
        self.timeout = float(timeout)
        self._seq = 0
        self._seq_lock = threading.Lock()
        # connections are per-thread: a blocking pull (sync-mode round
        # wait) in one thread must not serialize pushes from another
        # (the Communicator's send thread, grpc_client's channel pool role)
        self._tls = threading.local()
        self._all_socks = []
        self._all_lock = threading.Lock()
        # failover bookkeeping (shared across threads, under one lock):
        # last SERVER_INFO token per endpoint, the server round captured
        # when a restart was detected (consumed by the next pull), and
        # the cumulative per-endpoint round offset pulls subtract
        self._inc_lock = threading.Lock()
        self._incarnations = {}
        self._stale_pending = {}
        self._round_offset = {}
        self._no_info = set()     # endpoints without SERVER_INFO
        # elastic membership: the newest committed (epoch, shard map)
        # this client has seen — None until a resize fences us
        self._epoch = None
        self._map = None

    @staticmethod
    def _reconnect_budget():
        """Wall-clock budget for connection-refused/reset retries
        (pserver downtime under supervised failover): the supervisor's
        respawn backoff plus a fresh worker process's warm boot."""
        try:
            v = float(os.environ.get("PT_PS_RECONNECT_SECS", "60"))
        except ValueError:
            return 60.0
        import math as _math
        if not _math.isfinite(v):
            return 60.0
        return max(v, 0.0)

    def _next_seq(self):
        with self._seq_lock:
            self._seq += 1
            return self._seq

    def _sock(self, ep, fresh=False):
        socks = getattr(self._tls, "socks", None)
        if socks is None:
            socks = self._tls.socks = {}
        s = socks.get(ep)
        if fresh and s is not None:
            try:
                s.close()
            except OSError:
                pass
            s = None
        if s is None:
            host, port = ep.rsplit(":", 1)
            s = socket.create_connection((host, int(port)),
                                         timeout=self.timeout)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            socks[ep] = s
            with self._all_lock:
                self._all_socks.append(s)
            # a NEW connection is the only moment the server identity
            # can have changed under us — probe it before any frame
            # rides this socket
            self._note_incarnation(ep, s)
        return s

    def _note_incarnation(self, ep, s):
        """SERVER_INFO probe on a fresh connection: record the server's
        incarnation token; a CHANGE means the pserver restarted (it
        warm-booted from its last snapshot — updates since are gone)
        and arms the round resync the next pull consumes."""
        with self._inc_lock:
            if ep in self._no_info:
                return
        seq = self._next_seq()
        try:
            _send_frame(s, wire.SERVER_INFO, (), self.client_id, seq)
            rk, _, rseq, rf = _recv_frame(s)
        except (ConnectionError, socket.timeout, OSError,
                wire.WireError):
            # no reply at all — a dying server, not a legacy one;
            # surface as a connection failure so the caller's retry
            # path reconnects (and re-probes)
            self._drop_sock(ep)
            raise ConnectionError(
                f"pserver {ep}: SERVER_INFO probe got no reply")
        if rk != wire.OK_ARR or rseq != seq:
            # a pre-SERVER_INFO server rejects the unknown kind (ERR,
            # then closes the connection): remember it has no failover
            # probe and hand the caller a fresh socket
            with self._inc_lock:
                self._no_info.add(ep)
            self._drop_sock(ep)
            raise ConnectionError(
                f"pserver {ep}: no SERVER_INFO support (legacy "
                f"server); restart detection disabled")
        vals = np.asarray(rf[0]).ravel()
        inc, srv_round = int(vals[0]), int(vals[1])
        with self._inc_lock:
            prev = self._incarnations.get(ep)
            self._incarnations[ep] = inc
            if prev is not None and prev != inc:
                self._stale_pending[ep] = srv_round
                logging.getLogger("paddle_tpu.ps").warning(
                    "pserver %s restarted (incarnation %#x -> %#x): "
                    "serving round %d from its last snapshot; pulls "
                    "resync and lost rounds count in "
                    "ps_stale_rounds_total", ep, prev, inc, srv_round)

    def _effective_round(self, ep, min_round):
        """The round a pull should actually wait for: ``min_round``
        minus this endpoint's accumulated restart offset; a pending
        restart detection is consumed HERE, growing the offset by the
        rounds the reborn server lost (precise staleness — counted
        once, at the resync)."""
        with self._inc_lock:
            off = self._round_offset.get(ep, 0)
            want = min_round - off
            pend = self._stale_pending.get(ep)
            if pend is not None and want > pend:
                # consume the armed resync ONLY when this pull
                # actually outruns the reborn server: popping it on a
                # low-round pull (eval fetch, async min_round=0) would
                # disarm the resync and leave the NEXT training pull
                # deadlocking on a round the server will never reach —
                # the exact failure this machinery exists to prevent
                self._stale_pending.pop(ep, None)
                lost = want - pend
                self._round_offset[ep] = off + lost
                _m_stale_rounds.inc(lost)
                logging.getLogger("paddle_tpu.ps").warning(
                    "pserver %s: pull expected round %d but the "
                    "restarted server is at round %d — %d round(s) of "
                    "updates since its last snapshot were lost; "
                    "resuming from the snapshot round", ep, want, pend,
                    lost)
                want = pend
            return max(0, want)

    def _drop_sock(self, ep):
        """Close + forget the cached connection: a socket whose stream
        position is unknown (timeout, stale reply) must never be
        reused — a late reply would be consumed by the next call."""
        socks = getattr(self._tls, "socks", None)
        s = socks.pop(ep, None) if socks else None
        if s is not None:
            try:
                s.close()
            except OSError:
                pass
            with self._all_lock:
                if s in self._all_socks:
                    self._all_socks.remove(s)

    def _call(self, ep, kind, *fields):
        seq = self._next_seq()
        delay = self.BACKOFF
        attempts = 0            # transient failures (fixed budget)
        conn_failures = 0
        refused_deadline = None  # downtime failures (wall-clock budget)
        probed_map = False
        while True:
            try:
                s = self._sock(ep, fresh=conn_failures > 0)
                send_fields = fields
                if kind in (wire.PULL_PARAM, wire.PULL_PARAM_E):
                    # computed AFTER _sock: a reconnect's SERVER_INFO
                    # probe may have just armed the round resync this
                    # pull must consume
                    i = 1 if kind == wire.PULL_PARAM else 2
                    send_fields = fields[:i] + (self._effective_round(
                        ep, int(fields[i])),)
                _send_frame(s, kind, send_fields, self.client_id, seq)
                rk, _, rseq, rf = _recv_frame(s)
                if rseq != seq:
                    if rk == wire.ERR and rseq == 0:
                        # header-level rejection (bad magic/version/
                        # size): the server could not echo our seq —
                        # surface the typed error, don't burn retries
                        # re-sending the same bad frame
                        self._drop_sock(ep)
                        enforce(False, f"pserver {ep} error: "
                                       f"{rf[0] if rf else '?'}")
                    raise ConnectionError(
                        f"stale reply on {ep}: seq {rseq} != {seq}")
                break
            except (ConnectionError, socket.timeout, OSError,
                    wire.WireError) as e:
                self._drop_sock(ep)
                conn_failures += 1
                if isinstance(e, (ConnectionRefusedError,
                                  ConnectionResetError,
                                  BrokenPipeError)):
                    # pserver DOWNTIME (death, or supervised failover
                    # mid-respawn): a fixed attempt count would give up
                    # seconds into a restart that takes tens — retry
                    # against a wall-clock budget instead
                    now = time.monotonic()
                    if refused_deadline is None:
                        refused_deadline = (now
                                            + self._reconnect_budget())
                    if now >= refused_deadline:
                        raise
                    # a refused endpoint may be RETIRED (fleet shrink),
                    # not restarting: once per call, ask a surviving
                    # server for the committed map — if it is newer,
                    # re-route instead of burning the whole budget
                    if not probed_map:
                        probed_map = True
                        if self._maybe_probe_map(ep):
                            raise _Rerouted(
                                f"pserver {ep} unreachable and a newer "
                                f"shard map is committed")
                else:
                    attempts += 1
                    if attempts > self.MAX_RETRIES:
                        raise
                if _goodput._armed:
                    # reconnect backoff is time spent waiting on the
                    # fleet, not computing (goodput ledger)
                    _goodput.attribute(delay, phase="collective_wait")
                time.sleep(delay)
                delay = min(delay * 2, 2.0)
        if conn_failures:
            # the call survived at least one dropped/refused
            # connection — mutating frames stayed exactly-once via the
            # server's (client_id, seq) dedup
            _m_reconnects.inc()
        if rk == wire.WRONG_EPOCH:
            # the server fenced us: it applied NOTHING and handed back
            # the committed (epoch, map) — adopt and re-route
            self._adopt_map(int(rf[0]), rf[1])
            raise _Rerouted(f"pserver {ep} fenced request at epoch "
                            f"{int(rf[0])}")
        enforce(rk != wire.ERR, f"pserver {ep} error: "
                                f"{rf[0] if rf else '?'}")
        if rk == wire.OK_ARR:
            return rf[0]
        if rk == wire.OK_NAMES:
            return tuple(t.split("\n") if t else [] for t in rf)
        if rk == wire.OK_JSON:
            return rf[0]
        return None

    def _ep_of(self, name):
        ep = self.var_ep.get(name)
        enforce(ep is not None, f"var {name!r} not routed to any pserver")
        return ep

    # -- elastic routing (docs/ELASTIC_TRAINING.md "Resizing") -------------
    def _routing(self):
        with self._inc_lock:
            return self._epoch, self._map

    def _adopt_map(self, epoch, map_obj):
        """Adopt a committed (epoch, shard map) if strictly newer.
        Accepts the map as a dict or its JSON wire form."""
        if isinstance(map_obj, str):
            try:
                map_obj = json.loads(map_obj) if map_obj else None
            except ValueError:
                return False
        if not map_obj or "servers" not in map_obj:
            return False
        epoch = int(epoch)
        with self._inc_lock:
            if self._epoch is not None and epoch <= self._epoch:
                return False
            self._epoch, self._map = epoch, map_obj
        logging.getLogger("paddle_tpu.ps").info(
            "adopted fleet epoch %d (%d servers)", epoch,
            len(map_obj.get("servers", [])))
        return True

    def _maybe_probe_map(self, failed_ep):
        """Backstop for a RETIRED endpoint (fleet shrink): ask any
        surviving server for the committed map via EPOCH_MAP. Returns
        True iff a strictly newer map was adopted."""
        _, m = self._routing()
        eps = list(m.get("servers", [])) if m else list(self.endpoints)
        for ep in eps:
            if ep == failed_ep:
                continue
            try:
                host, port = ep.rsplit(":", 1)
                s = socket.create_connection((host, int(port)),
                                             timeout=2.0)
                try:
                    s.setsockopt(socket.IPPROTO_TCP,
                                 socket.TCP_NODELAY, 1)
                    seq = self._next_seq()
                    _send_frame(s, wire.EPOCH_MAP, (),
                                self.client_id, seq)
                    rk, _, rseq, rf = _recv_frame(s)
                finally:
                    s.close()
                if rk != wire.OK_JSON or rseq != seq:
                    continue
                obj = json.loads(rf[0])
                if obj.get("map"):
                    return self._adopt_map(int(obj.get("epoch", 0)),
                                           obj["map"])
                return False
            except (ConnectionError, socket.timeout, OSError,
                    wire.WireError, ValueError):
                continue
        return False

    def _routed(self, fn):
        """Run ``fn`` (which routes off the current map), re-running it
        on _Rerouted — each fence refreshes the map, so the route
        converges on the committed epoch."""
        last = None
        for n in range(20):
            try:
                return fn()
            except _Rerouted as e:
                last = e
                time.sleep(min(0.05 * (n + 1), 0.5))
        enforce(False, f"pserver request never settled on a fleet "
                       f"epoch after 20 re-routes (last: {last})")

    def _dense_ep(self, name):
        """(epoch, endpoint) for a dense var: the committed map when we
        have one and it routes this var, else the static transpile-time
        placement with epoch None (legacy, unfenced frame kinds)."""
        epoch, m = self._routing()
        if m is None or name not in m.get("dense", {}):
            return None, self._ep_of(name)
        return epoch, m["dense"][name]

    def _sparse_route(self, table, ids):
        """[(epoch, endpoint, positions-or-None)] covering ``ids``.
        positions None means "all of ids" (the single-route legacy
        path). Always non-empty, even for empty ids."""
        epoch, m = self._routing()
        owners = (m.get("sparse") or {}).get(table) if m else None
        if owners is None:
            return [(None, self._ep_of(table), None)]
        from paddle_tpu.distributed import membership as mb
        vs = mb.vshard_of(ids)
        groups = {}
        for v in np.unique(vs):
            ep = owners.get(str(int(v))) or self._ep_of(table)
            groups.setdefault(ep, []).append(int(v))
        out = [(epoch, ep,
                np.flatnonzero(np.isin(vs, np.asarray(groups[ep],
                                                      np.int64))))
               for ep in sorted(groups)]
        if not out:
            eps = sorted(set(owners.values()))
            out = [(epoch, eps[0] if eps else self._ep_of(table),
                    np.zeros(0, np.int64))]
        return out

    # -- dense -------------------------------------------------------------
    def push_grad(self, name, grad):
        g = np.asarray(grad)

        def go():
            epoch, ep = self._dense_ep(name)
            if epoch is None:
                self._call(ep, wire.PUSH_GRAD, name, self.trainer_id,
                           g)
            else:
                self._call(ep, wire.PUSH_GRAD_E, epoch, name,
                           self.trainer_id, g)
        self._routed(go)

    def pull_param(self, name, min_round=0):
        def go():
            epoch, ep = self._dense_ep(name)
            if epoch is None:
                return self._call(ep, wire.PULL_PARAM, name, min_round)
            return self._call(ep, wire.PULL_PARAM_E, epoch, name,
                              min_round)
        return self._routed(go)

    # -- sparse (parameter_prefetch.cc parity) -----------------------------
    def pull_sparse(self, table, ids):
        ids = np.asarray(ids, np.int64)
        buf = [None]

        def fetch(pos, depth=0):
            enforce(depth < 20, f"sparse pull on {table!r} never "
                                f"settled on a fleet epoch")
            sub_ids = ids if pos is None else ids[pos]
            for epoch, ep, idx in self._sparse_route(table, sub_ids):
                if pos is None:
                    p = idx
                elif idx is None:
                    p = pos
                else:
                    p = pos[idx]
                si = ids if p is None else ids[p]
                try:
                    if epoch is None:
                        sub = self._call(ep, wire.PULL_SPARSE, table,
                                         si)
                    else:
                        sub = self._call(ep, wire.PULL_SPARSE_E,
                                         epoch, table, si)
                except _Rerouted:
                    time.sleep(min(0.05 * (depth + 1), 0.5))
                    fetch(p, depth + 1)
                    continue
                sub = np.asarray(sub)
                if p is None:
                    buf[0] = sub
                    return
                if buf[0] is None:
                    buf[0] = np.zeros((ids.size,) + sub.shape[1:],
                                      sub.dtype)
                buf[0][p] = sub
        fetch(None)
        return buf[0]

    def push_sparse(self, table, ids, grads, lr=None):
        ids = np.asarray(ids, np.int64)
        grads = np.asarray(grads)

        def send(pos, depth=0):
            # only the fenced GROUP is re-sent (the fenced server
            # applied nothing), so a re-route mid-multi-server push
            # never double-applies the groups that already landed
            enforce(depth < 20, f"sparse push on {table!r} never "
                                f"settled on a fleet epoch")
            sub_ids = ids if pos is None else ids[pos]
            for epoch, ep, idx in self._sparse_route(table, sub_ids):
                if pos is None:
                    p = idx
                elif idx is None:
                    p = pos
                else:
                    p = pos[idx]
                si = ids if p is None else ids[p]
                sg = grads if p is None else grads[p]
                try:
                    if epoch is None:
                        self._call(ep, wire.PUSH_SPARSE, table, si,
                                   sg, lr)
                    else:
                        self._call(ep, wire.PUSH_SPARSE_E, epoch,
                                   table, si, sg, lr)
                except _Rerouted:
                    time.sleep(min(0.05 * (depth + 1), 0.5))
                    send(p, depth + 1)
        send(None)

    def shrink_table(self, table, max_age):
        """FleetWrapper::ShrinkSparseTable parity: evict rows untouched
        for more than ``max_age`` pull/push calls. Returns evicted
        count (summed across owners when the table is resharded)."""
        def go():
            _, m = self._routing()
            owners = (m.get("sparse") or {}).get(table) if m else None
            eps = sorted(set(owners.values())) if owners \
                else [self._ep_of(table)]
            total = 0
            for ep in eps:
                out = self._call(ep, wire.SHRINK_TABLE, table,
                                 int(max_age))
                total += int(np.asarray(out).ravel()[0])
            return total
        return self._routed(go)

    # -- control -----------------------------------------------------------
    def _all_eps(self):
        _, m = self._routing()
        return list(m["servers"]) if m else list(self.endpoints)

    def barrier(self, tag="global"):
        def go():
            for ep in self._all_eps():
                self._call(ep, wire.BARRIER, tag, self.trainer_id)
        if _goodput._armed:
            # barrier wall time = waiting for the slowest peer
            # (goodput ledger's collective_wait / straggler phase)
            _t_gp = time.perf_counter()
            try:
                self._routed(go)
            finally:
                _goodput.attribute(time.perf_counter() - _t_gp,
                                   phase="collective_wait")
            return
        self._routed(go)

    def checkpoint_notify(self, dirname):
        for ep in self._all_eps():
            self._call(ep, wire.CHECKPOINT_NOTIFY, dirname)

    def list_vars(self, ep=None):
        return self._call(ep or self._all_eps()[0], wire.LIST_VARS)

    def server_info(self, ep=None):
        """(incarnation, min dense round) of one pserver — the
        failover probe, also sent automatically on every fresh
        connection (see ``_note_incarnation``)."""
        out = self._call(ep or self.endpoints[0], wire.SERVER_INFO)
        vals = np.asarray(out).ravel()
        return int(vals[0]), int(vals[1])

    def stop_servers(self):
        for ep in self._all_eps():
            try:
                self._call(ep, wire.STOP)
            except Exception:
                pass

    def close(self):
        with self._all_lock:
            for s in self._all_socks:
                try:
                    s.close()
                except OSError:
                    pass
            self._all_socks.clear()
        self._tls = threading.local()


class Communicator:
    """Async trainer-side grad sender (communicator.h:160 parity): grads
    queue up per var, a background thread merges (sums) pending grads per
    var and pushes merged updates — send_queue semantics of MergeVars."""

    def __init__(self, client, merge_steps=1):
        self.client = client
        self.merge_steps = max(int(merge_steps), 1)
        self._pending = {}
        self._counts = {}
        self._cv = threading.Condition()
        self._stop = False
        self._thread = threading.Thread(target=self._loop, daemon=True)

    def start(self):
        self._thread.start()
        return self

    def send(self, name, grad):
        with self._cv:
            g = np.asarray(grad)
            if name in self._pending:
                self._pending[name] = self._pending[name] + g
            else:
                self._pending[name] = g.copy()
            self._counts[name] = self._counts.get(name, 0) + 1
            self._cv.notify()

    def _drain(self):
        ready = {}
        for n, c in list(self._counts.items()):
            if c >= self.merge_steps or self._stop:
                ready[n] = self._pending.pop(n) / c
                del self._counts[n]
        return ready

    def _loop(self):
        while True:
            with self._cv:
                self._cv.wait_for(
                    lambda: self._stop or any(
                        c >= self.merge_steps for c in self._counts.values()),
                    timeout=0.5)
                ready = self._drain()
                done = self._stop and not self._counts
            for n, g in ready.items():
                self.client.push_grad(n, g)
            if done:
                return

    def flush(self):
        with self._cv:
            ready = {n: self._pending.pop(n) / self._counts.pop(n)
                     for n in list(self._counts)}
        for n, g in ready.items():
            self.client.push_grad(n, g)

    def stop(self):
        with self._cv:
            self._stop = True
            self._cv.notify()
        self._thread.join(timeout=10.0)


def _maybe_ps_exporter():
    """A RankExporter for THIS pserver process when launched under a
    supervisor (PT_PS_METRICS_DIR, set by launch_ps — deliberately
    NOT PADDLE_HEARTBEAT_DIR, which the launcher reserves for
    trainers so a role-shared script's ``from_env`` hookups can never
    clobber a trainer's files): snapshots land at
    ``rank<worker_num + index>.prom`` — offset past the trainer
    ranks, because pservers share the trainer id numbering and
    ``rank<i>.prom`` would collide with trainer i's. The launcher's
    job aggregation reads every rank*.prom, so the pserver-side
    snapshot metrics reach the job-level metrics.prom; the hang
    watchdog only consults ranks < worker_num, so the offset files
    never vouch for liveness."""
    d = os.environ.get("PT_PS_METRICS_DIR")
    if not d or os.environ.get("TRAINING_ROLE") != "PSERVER":
        return None
    try:
        from paddle_tpu.distributed import health
        from paddle_tpu.monitor.exporter import RankExporter
        rank = (int(os.environ.get("PADDLE_TRAINERS_NUM", "0") or 0)
                + int(os.environ.get("PADDLE_TRAINER_ID", "0") or 0))
        return RankExporter(health.metrics_path(d, rank),
                            interval=1.0).start()
    except Exception:
        return None             # telemetry must not block serving


def _ps_reconcile_epoch(server, state_dir, meta):
    """Warm-boot membership reconcile: line this server up with the
    committed fleet epoch. The snapshot meta carries the epoch + map
    the server was serving when it last saved; ``fleet_epoch.json`` is
    the fleet's single source of truth. If the file is AHEAD of the
    snapshot, this server crashed between the coordinator's commit
    publish and its own post-commit snapshot — adopt the verified
    shadows the file says we won, retire what we lost, and serve the
    committed epoch. All remaining shadows for our tag are then swept:
    at-or-below the committed epoch they are consumed, above it they
    are debris of a migration whose coordinator will abort or restage."""
    from paddle_tpu.distributed import membership as mb
    from paddle_tpu import io_checkpoint as ioc
    server.state_dir = state_dir
    server.epoch = int((meta or {}).get("epoch", 0) or 0)
    server.shard_map = (meta or {}).get("shard_map") or None
    ef = mb.load_epoch_file(state_dir)
    if ef and int(ef.get("epoch", 0)) > server.epoch:
        epoch, new_map = int(ef["epoch"]), ef["map"]
        tag = mb.tag_of_ep(server.endpoint)
        adopted = 0
        for path, _, ep, _ in mb.list_shadows(state_dir, tag=tag):
            if ep != epoch:
                continue
            try:
                manifest, arrays = ioc.verify_npz(path)
            except Exception as e:                  # noqa: BLE001
                _ps_log(f"ignoring unreadable shadow {path}: {e}")
                continue
            unit = (manifest or {}).get("unit")
            if not unit or not _unit_owned_by(new_map, unit,
                                              server.endpoint):
                continue
            adopted += server._adopt_unit(unit, arrays)
        server._retire_units(new_map)
        server.epoch, server.shard_map = epoch, new_map
        if adopted:
            _m_migrated.inc(adopted)
            # persist the adoption before sweeping its shadows: until
            # a snapshot holds these rows the shadows are the only
            # durable copy, and a crash here must find them again
            try:
                server.save(state_dir)
            except Exception as e:                  # noqa: BLE001
                _ps_log(f"post-reconcile snapshot failed ({e}); "
                        f"keeping staged shadows")
                _m_epoch.set(server.epoch)
                return
        _ps_log(f"reconciled to committed fleet epoch {epoch} "
                f"(adopted {adopted} rows from staged shadows)")
    server._sweep_my_shadows()
    tag = mb.tag_of_ep(server.endpoint)
    swept = 0
    for fn in _ps_listdir(state_dir):
        if fn.startswith(f".psshadow_{tag}.") \
                and fn.endswith(".tmp.npz"):
            try:
                os.remove(os.path.join(state_dir, fn))
                swept += 1
            except OSError:
                pass
    if swept:
        _ps_log(f"swept {swept} torn shadow temp file(s)")
    _m_epoch.set(server.epoch)


def run_pserver(pserver_program, state_dir=None, snapshot_secs=None,
                on_server=None, recipes=None):
    """Build + run a blocking ParameterServer from a transpiled
    PServerProgram (the exe.run(pserver_prog) role in §3.3).

    Failover wiring (docs/ELASTIC_TRAINING.md "Pserver failover"):
    with ``state_dir`` (or ``PT_PS_SNAPSHOT_DIR``, exported by
    ``launch_ps --ps_snapshot_secs``) the server WARM-BOOTS from its
    newest integrity-verified snapshot generation before serving —
    quarantining and walking back past corrupt ones — then keeps a
    periodic background snapshot every ``snapshot_secs`` (or
    ``PT_PS_SNAPSHOT_SECS``, default 5 s) plus a final flush on
    graceful stop. ``on_server`` (if given) is called with the built
    server after the warm boot, before serving — the hook chaos tests
    use to install ``testing.faults.install_ps_faults``.

    Elastic membership (``PT_PS_ELASTIC``, set by ``launch_ps
    --ps_max_servers/--ps_min_servers``): forces the python transport
    (the native server has no migration handlers), hands the server
    its hosting ``recipes`` (specs for units it may ADOPT in a future
    resize without hosting them today), and reconciles the warm boot
    against ``fleet_epoch.json`` — see ``_ps_reconcile_epoch``."""
    elastic = bool(os.environ.get("PT_PS_ELASTIC"))
    if elastic:
        from paddle_tpu.core.flags import set_flags
        set_flags({"ps_transport": "python"})
    server = pserver_program.build_server()
    if isinstance(server, ParameterServer):
        server.recipes = dict(recipes or {})
    state_dir = state_dir or os.environ.get("PT_PS_SNAPSHOT_DIR") or None
    exporter = _maybe_ps_exporter()
    if state_dir:
        try:
            meta = server.load(state_dir)
        except OSError as e:
            # a transient I/O error that persisted through retries is
            # NOT corruption (the PR-5 rule): serving initial values
            # would silently discard training, so crash into the
            # supervisor's restart budget and let the respawn retry
            # the read
            _ps_log(f"warm boot failed on an I/O error "
                    f"({type(e).__name__}: {e}); exiting so the "
                    f"supervisor's restart budget can retry the read "
                    f"(a blip is not corruption)")
            raise
        except Exception as e:
            _ps_log(f"warm boot failed ({type(e).__name__}: {e}); "
                    f"starting from initial values")
            meta = None
        if meta is not None:
            _ps_log(f"warm boot: restored pserver state generation "
                    f"{meta.get('gen')} (written by incarnation "
                    f"{meta.get('incarnation', 0):#x}) from "
                    f"{state_dir}; now serving as incarnation "
                    f"{server.incarnation:#x}")
        else:
            _ps_log(f"no restorable pserver snapshot in {state_dir}; "
                    f"starting from initial values")
        if elastic and isinstance(server, ParameterServer):
            _ps_reconcile_epoch(server, state_dir, meta)
        if snapshot_secs is None:
            try:
                snapshot_secs = float(
                    os.environ.get("PT_PS_SNAPSHOT_SECS") or 5.0)
            except ValueError:
                snapshot_secs = 5.0
        server.start_snapshots(state_dir, snapshot_secs)
    if on_server is not None:
        on_server(server)
    try:
        server.run()
    finally:
        if state_dir:
            server.stop_snapshots(final_save=True)
        if exporter is not None:
            exporter.stop()
    return server
