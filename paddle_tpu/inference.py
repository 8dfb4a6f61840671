"""Inference stack: Config + Predictor over frozen programs.

Parity: the reference's inference/ side stack — AnalysisConfig
(inference/api/analysis_config.cc), AnalysisPredictor with ZeroCopyTensor
I/O (inference/api/analysis_predictor.h:46,56,68), the analysis pass
pipeline (inference/analysis/passes/passes.cc), and NaiveExecutor's
lock-free per-op loop (framework/naive_executor.cc).

TPU-native shape: a frozen program compiles AHEAD OF TIME into ONE XLA
computation per input-shape signature (the per-op NaiveExecutor loop and
the TRT subgraph engine both collapse into whole-program XLA); compiled
executables are cached per shape bucket, so serving at a handful of batch
sizes pays compilation once each. "Zero copy" here is jax.device_put
into the executable's donated input layout.
"""

import hashlib
import json
import os
import threading
import time
import zlib

import numpy as np

from paddle_tpu.core.enforce import enforce
from paddle_tpu.core.place import CPUPlace
from paddle_tpu.static.executor import Executor, Scope, exec_op
from paddle_tpu.static import io as static_io

__all__ = ["Config", "Predictor", "create_predictor", "ZeroCopyTensor",
           "export_aot", "verify_aot_dir", "read_aot_version",
           "load_quantized_params", "AOTIntegrityError"]

AOT_DIR = "__aot__"
AOT_INDEX = "index.json"


def _build_pure_fn(program, feed_names, fetch_names):
    """A jittable fn(params_tuple, feeds_tuple) -> fetches_tuple over a
    frozen (host-op-free) inference program. Param/feed orders are the
    sorted state names / the given feed order — recorded in the AOT
    index so a loader binds buffers without re-reading the program."""
    import jax

    blk = program.global_block()
    ops = list(blk.ops)
    enforce(not any(op.attrs.get("_host") for op in ops),
            "AOT export requires a host-op-free inference program")
    constants = dict(getattr(program, "_constants", {}))
    state_names = sorted(n for n, v in blk.vars.items()
                         if v.persistable and n not in constants)
    seed = program.random_seed

    def fn(params, feeds):
        env = dict(constants)
        env.update(zip(state_names, params))
        env.update(zip(feed_names, feeds))
        key = None
        for i, op in enumerate(ops):
            if op.attrs.get("_needs_rng"):
                if key is None:
                    # match the Executor's derivation at its first run
                    # (fold_in(base, step_idx=0) then per-op index; no
                    # host ops here, so no index adjustment). Inference
                    # is stateless: every AOT call draws step-0 keys.
                    key = jax.random.fold_in(
                        jax.random.PRNGKey(seed), 0)
                # an optimized program (opt_passes) pins each rng op's
                # pre-pass index in _rng_idx so masks match the
                # unoptimized lowering
                k = jax.random.fold_in(
                    key, op.attrs.get("_rng_idx", i))
            else:
                k = None
            env.update(exec_op(op, env, k))
        return tuple(env[n] for n in fetch_names)

    return fn, state_names


def _program_hash(program):
    """Fingerprint of the frozen program: AOT index entries are valid
    only for the exact graph they were compiled from. Canonical
    structural hash (static/serialize.py) — stable across
    interpreter/numpy versions, unlike the r2 pickle-bytes hash whose
    drift silently disabled the AOT fast path (ADVICE-r2)."""
    from paddle_tpu.static.serialize import program_fingerprint

    return program_fingerprint(program)[:16]


_XLA_MAGIC = b"PTXLA1"


def _aot_treedefs(n_params, n_feeds, n_out):
    """Rebuild the jit call's (in_tree, out_tree) from leaf counts —
    the fn signature is fn(params_tuple, feeds_tuple) -> outputs_tuple,
    so the tree-defs are fully determined by the counts and never need
    to be pickled into the artifact."""
    import jax

    in_tree = jax.tree.structure(
        ((tuple(range(n_params)), tuple(range(n_feeds))), {}))
    out_tree = jax.tree.structure(tuple(range(n_out)))
    return in_tree, out_tree


def _sig_of(feed_names, shaped):
    """Signature entry for one shape bucket: [[name, shape, dtype]...]
    in feed order. ``shaped``: {name: array-or-(shape, dtype)}."""
    sig = []
    for n in feed_names:
        v = shaped[n]
        if isinstance(v, tuple):
            shape, dtype = v
        else:
            shape, dtype = np.shape(v), np.asarray(v).dtype
        sig.append([n, [int(d) for d in shape], np.dtype(dtype).name])
    return sig


def _sig_key(sig):
    return hashlib.sha256(json.dumps(sig).encode()).hexdigest()[:16]


class AOTIntegrityError(RuntimeError):
    """An AOT artifact failed its integrity manifest (CRC/size drift or
    a missing file): positive evidence of a torn or bit-rotted export,
    named precisely — distinct from the silent degrade-to-retrace path
    taken for wrong-platform/wrong-version artifacts."""


class AOTVerifyResult(int):
    """``verify_aot_dir``'s return value: the number of artifact files
    verified (an int, so every existing ``== N`` caller keeps working)
    plus the ``model_version`` the manifest declares (``None`` for
    legacy/absent indexes). The version is what the serving hot-swap
    gate compares against the live server (docs/SERVING.md
    "Hot model swap")."""

    def __new__(cls, verified, model_version=None):
        self = super().__new__(cls, int(verified))
        self.model_version = model_version
        return self


def _model_version_of(prog_hash, state_names, params):
    """Deterministic content hash of (program, weights) plus an export
    timestamp: ``<sha256[:12]>.<unix-microseconds>``. Two exports of
    identical content get distinct versions (the timestamp is the
    publish event — a republish is a deliberate deploy signal for
    ``watch_dir`` mode), while the hash half answers "is this the same
    model bits" for operators reading logs."""
    h = hashlib.sha256(prog_hash.encode())
    for n, p in zip(state_names, params):
        h.update(n.encode())
        h.update(str(p.shape).encode())
        h.update(np.dtype(p.dtype).name.encode())
        h.update(np.ascontiguousarray(p).tobytes())
    return f"{h.hexdigest()[:12]}.{int(time.time() * 1e6)}"


def _file_integrity(path):
    """{"crc32", "nbytes"} of a file's byte image (the io_checkpoint
    idiom, applied to opaque artifact files)."""
    crc = 0
    n = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                break
            crc = zlib.crc32(chunk, crc)
            n += len(chunk)
    return {"crc32": crc & 0xFFFFFFFF, "nbytes": n}


def _verify_artifact(path, expect):
    """Verify one artifact file against its manifest record; raises
    :class:`AOTIntegrityError` naming the file and the first mismatch."""
    name = os.path.basename(path)
    try:
        got = _file_integrity(path)
    except FileNotFoundError:
        raise AOTIntegrityError(
            f"AOT artifact {name!r} is missing but listed in the "
            f"integrity manifest — torn export; re-run export_aot")
    if got["nbytes"] != expect["nbytes"]:
        raise AOTIntegrityError(
            f"AOT artifact {name!r} failed integrity: size "
            f"{got['nbytes']} != manifest {expect['nbytes']} — torn "
            f"export or concurrent rewrite; re-run export_aot")
    if got["crc32"] != expect["crc32"]:
        raise AOTIntegrityError(
            f"AOT artifact {name!r} failed integrity: CRC32 "
            f"{got['crc32']:#010x} != manifest "
            f"{expect['crc32']:#010x} — bit rot or torn export; "
            f"re-run export_aot")


def _version_from_entries(entries):
    """The manifest's model version: the NEWEST per-entry stamp by
    publish timestamp (the ``.<unix-micros>`` suffix). An index merged
    across exports keeps older entries with older stamps — the latest
    export is the dir's deploy identity."""
    best, best_ts = None, -1
    for e in entries if isinstance(entries, list) else []:
        if not isinstance(e, dict):
            continue
        v = e.get("model_version")
        if not v:
            continue
        try:
            ts = int(str(v).rsplit(".", 1)[1])
        except (IndexError, ValueError):
            ts = 0
        if ts >= best_ts:
            best, best_ts = v, ts
    return best


def verify_aot_dir(model_dir):
    """Verify every AOT artifact under ``<model_dir>/__aot__`` against
    the index's integrity manifest. Returns an :class:`AOTVerifyResult`
    — an int (the number of files verified; 0 when there is no AOT
    index, or for legacy indexes without integrity records — nothing to
    vouch for) carrying ``model_version`` (the manifest's declared
    version, or None); raises :class:`AOTIntegrityError` on the first
    bad file. The serving server runs this at warm boot AND at every
    hot-swap gate (``InferenceServer.swap``) so corruption fails at
    load/swap time, not mid-traffic."""
    aot_dir = os.path.join(model_dir or "", AOT_DIR)
    index_path = os.path.join(aot_dir, AOT_INDEX)
    if not os.path.exists(index_path):
        return AOTVerifyResult(0)
    try:
        with open(index_path) as f:
            entries = json.load(f)
    except (OSError, ValueError) as e:
        raise AOTIntegrityError(
            f"AOT index {index_path!r} is unreadable ({e}); re-run "
            f"export_aot")
    verified = 0
    for e in entries if isinstance(entries, list) else []:
        if not isinstance(e, dict):
            continue
        for name, rec in sorted(e.get("integrity", {}).items()):
            _verify_artifact(os.path.join(aot_dir, name), rec)
            verified += 1
    return AOTVerifyResult(verified, _version_from_entries(entries))


def load_quantized_params(model_dir):
    """The quantized-serving sidecar of ``export_aot(quantize=...)``,
    or None when the dir has no quantized export. Returns
    ``{"mode", "weights", "values"}`` where ``values`` maps each
    quantized weight (and its ``@quant_scale`` table for int8) to the
    stored array. The sidecar's CRC is part of the integrity manifest —
    run ``verify_aot_dir`` first (the serving boot/swap gate does);
    this loader re-checks the file against the newest entry's record
    so a direct caller can't load tampered scales either. The WEIGHT
    LIST comes from the manifest, never re-derived — the loader applies
    exactly what the exporter quantized (static/opt_passes.
    apply_weight_quant refuses on mismatch)."""
    index_path = os.path.join(model_dir or "", AOT_DIR, AOT_INDEX)
    try:
        with open(index_path) as f:
            entries = json.load(f)
    except (OSError, ValueError):
        return None
    # the NEWEST export overall decides, not the newest export that
    # happens to carry a quant block: a later fp32 re-export under a
    # different shape-bucket set leaves older entries in the index
    # (key-based pruning), and serving its stale sidecar would
    # silently overwrite the freshly loaded fp32 weights
    best, best_ts = None, -1
    for e in entries if isinstance(entries, list) else []:
        if not isinstance(e, dict):
            continue
        v = e.get("model_version")
        try:
            ts = int(str(v).rsplit(".", 1)[1])
        except (IndexError, ValueError, AttributeError):
            ts = 0
        if ts > best_ts or (
                ts == best_ts
                and isinstance(e.get("quant"), dict)
                and not isinstance((best or {}).get("quant"), dict)):
            best, best_ts = e, ts
    if best is None or not isinstance(best.get("quant"), dict):
        return None
    q = best["quant"]
    qpath = os.path.join(model_dir, AOT_DIR, q.get("file", ""))
    rec = (best.get("integrity") or {}).get(q.get("file"))
    if not rec:
        # quant sidecars have carried integrity records since the
        # feature shipped — an entry without one is a doctored index,
        # not a legacy artifact; refusing beats loading unverifiable
        # scale tables
        raise AOTIntegrityError(
            f"quantized sidecar {q.get('file')!r} has no integrity "
            f"record in the AOT index; treating as tampered — re-run "
            f"export_aot")
    _verify_artifact(qpath, rec)
    try:
        with np.load(qpath) as z:
            values = {k: z[k] for k in z.files}
    except (OSError, ValueError) as e:
        raise AOTIntegrityError(
            f"quantized sidecar {qpath!r} is unreadable ({e}); "
            f"re-run export_aot")
    mode = q.get("mode")
    weights = list(q.get("weights", []))
    if mode == "bf16":
        import jax.numpy as jnp
        values = {k: (v.view(jnp.bfloat16) if k in weights else v)
                  for k, v in values.items()}
    return {"mode": mode, "weights": weights, "values": values}


def read_aot_version(model_dir):
    """The manifest's ``model_version`` WITHOUT verifying artifact
    CRCs — a cheap index-only probe (one small JSON read) for the
    hot-swap directory watcher, which polls it every interval; the
    full CRC pass runs once, at the swap gate. Returns None when the
    dir has no AOT index, the index is unreadable, or the export
    predates versioning."""
    index_path = os.path.join(model_dir or "", AOT_DIR, AOT_INDEX)
    try:
        with open(index_path) as f:
            return _version_from_entries(json.load(f))
    except (OSError, ValueError):
        return None


def export_aot(dirname, program, feed_names, fetch_names, scope,
               shape_buckets, platforms=("cpu", "tpu"), quantize=None,
               apply_passes=None):
    """Compile the frozen program per shape bucket and serialize BOTH
    artifacts (the VERDICT-r1 'inference artifact export' gap; ref
    capability: inference/io.cc + analysis_predictor.h:46 serialize an
    optimized deployable model):

    - <h>.xla — the platform-native compiled executable
      (jax.experimental.serialize_executable): loading skips tracing
      AND XLA compilation, but pins platform + jax version;
    - <h>.shlo — portable StableHLO (jax.export): loading skips Python
      retracing/program analysis; XLA compiles once at load.

    ``shape_buckets``: list of {feed name: (shape, dtype)} (or example
    arrays). ``platforms`` lowers the portable export for each named
    platform (default cpu+tpu) so the .shlo artifact really is
    cross-platform. Returns the index entries.

    ``apply_passes`` (default: ``FLAGS_apply_ir_passes``) runs the
    program-level optimization pipeline (static/opt_passes.py) on a
    clone of the frozen program before compiling.

    ``quantize="int8"|"bf16"`` additionally performs weight-only
    post-training quantization (docs/SERVING.md "Quantized serving"):
    every eligible matmul weight is stored quantized (int8: per-output-
    channel abs-max scales; bf16: storage cast) in a ``quant.<mode>.npz``
    sidecar under ``__aot__`` — covered by the integrity manifest, so
    a tampered scale table fails ``verify_aot_dir`` — and the dequant
    is folded into the consuming matmul as one ``fused_matmul`` op.
    The serving warm boot (``InferenceServer``/``swap``) loads such a
    dir transparently with int8-resident params; the single-request
    ``Predictor`` keeps using the fp32 params file."""
    import jax
    import jax.export
    from jax.experimental import serialize_executable as se

    from paddle_tpu.core.flags import get_flag
    from paddle_tpu.static import opt_passes as _opt

    if apply_passes is None:
        apply_passes = bool(get_flag("apply_ir_passes"))
    # the deploy identity is the CALLER's program — the same graph
    # save_inference_model wrote. The Predictor matches entries by the
    # hash of the loaded __model__, which never sees the pass/quant
    # rewrites below, so hashing the rewritten clone would orphan
    # every entry into the silent retrace path.
    prog_hash = _program_hash(program)
    if apply_passes:
        program = _opt.optimize_inference(program, fetch_names)
    out_dir = os.path.join(dirname, AOT_DIR)
    os.makedirs(out_dir, exist_ok=True)
    overlay = {}
    qmeta = None
    if quantize is not None:
        enforce(quantize in ("int8", "bf16"),
                f"quantize must be 'int8' or 'bf16', got {quantize!r}")
        blk = program.global_block()
        values = {n: np.asarray(scope.find_var(n))
                  for n, v in blk.vars.items()
                  if getattr(v, "persistable", False)
                  and scope.find_var(n) is not None}
        plan = _opt.plan_weight_quant(program, values, quantize)
        enforce(plan,
                f"quantize={quantize!r}: no eligible weight found "
                f"(2-D persistable float32 consumed only as a "
                f"matmul/mul RHS in [in, out] layout)")
        program = _opt.apply_weight_quant(program, plan, quantize)
        overlay = _opt.quantize_weight_values(values, plan, quantize)
        # per-export filename (the {h}.xla idiom): a FIXED name would
        # let a later quantized re-export overwrite the file older
        # surviving index entries still record CRCs for (npz bytes are
        # not reproducible — zip headers embed mtimes), and
        # verify_aot_dir would then refuse the whole dir after a
        # legitimate export. Dropped entries' sidecars are unlinked by
        # the live_files sweep below.
        qfile = f"quant.{quantize}.{time.time_ns() // 1000}.npz"
        qtmp = os.path.join(out_dir, f".{qfile}.{os.getpid()}.tmp")
        with open(qtmp, "wb") as f:
            # bf16 has no stable npz dtype (numpy reloads it as void):
            # store the raw 16-bit lanes; the loader views them back
            np.savez(f, **{
                k: (np.asarray(v).view(np.uint16)
                    if quantize == "bf16" and k in plan else v)
                for k, v in overlay.items()})
        os.replace(qtmp, os.path.join(out_dir, qfile))
        qmeta = {
            "mode": quantize, "file": qfile, "weights": sorted(plan),
            # per-weight scale-table digests: the manifest names the
            # exact scale bytes a loader must see (the file CRC in
            # `integrity` is the enforcement; this is the evidence an
            # operator can diff across exports)
            "scales_sha256": {
                w: hashlib.sha256(np.ascontiguousarray(
                    overlay[w + _opt.QUANT_SCALE_SUFFIX])
                    .tobytes()).hexdigest()[:16]
                for w in plan} if quantize == "int8" else {},
        }

    fn, state_names = _build_pure_fn(program, feed_names, fetch_names)
    raw = [overlay.get(n, scope.find_var(n)) for n in state_names]
    missing = [n for n, v in zip(state_names, raw) if v is None]
    enforce(not missing,
            f"scope missing persistables for AOT export: {missing[:5]}")
    params = tuple(np.asarray(v) for v in raw)
    param_sds = tuple(jax.ShapeDtypeStruct(p.shape, p.dtype)
                      for p in params)
    jitted = jax.jit(fn)
    entries = []
    platform = jax.devices()[0].platform
    # the deploy identity of THIS export (content hash + publish
    # timestamp), stamped on every entry — the serving hot-swap
    # gate/watcher reads the newest stamp back via
    # verify_aot_dir/read_aot_version
    model_version = _model_version_of(prog_hash, state_names, params)
    for bucket in shape_buckets:
        sig = _sig_of(feed_names, bucket)
        feed_sds = tuple(
            jax.ShapeDtypeStruct(tuple(s), np.dtype(dt))
            for _, s, dt in sig)
        # the key covers the PROGRAM too: a re-saved model must never
        # serve a stale graph from a surviving shape bucket
        h = _sig_key(sig + [["__program__", [], prog_hash]])
        compiled = jitted.lower(param_sds, feed_sds).compile()
        try:
            # compile-time memory ledger: each bucket's footprint is
            # a capacity-planning number the swap admission and the
            # postmortems read back (monitor/memory.py)
            from paddle_tpu.monitor import memory as _memory
            _memory.record_segment_memory(
                ("export", prog_hash), bucket,
                _memory.analyze_compiled(compiled))
        except Exception:
            pass
        # the unsharded jit above compiles single-device; recorded so
        # the loader binds the executable to exactly that many devices
        entry = {"sig": sig, "key": h, "platform": platform,
                 "jax_version": jax.__version__,
                 "program_hash": prog_hash,
                 "model_version": model_version,
                 "state_names": state_names, "num_devices": 1}
        payload, in_tree, out_tree = se.serialize(compiled)
        # the wrapper is a structural container (header + counts +
        # payload), NOT a pickle: tree-defs are rebuilt from counts at
        # load. The payload itself is jax's serialize_executable blob —
        # deserializing it is jax's trust boundary (see Predictor docs).
        expect_in, expect_out = _aot_treedefs(
            len(param_sds), len(feed_sds), len(fetch_names))
        enforce(expect_in == in_tree and expect_out == out_tree,
                "AOT treedef layout drifted from (params, feeds) -> "
                "outputs tuples; container format needs updating")
        meta = json.dumps({"n_params": len(param_sds),
                           "n_feeds": len(feed_sds),
                           "n_out": len(fetch_names)}).encode("utf-8")
        with open(os.path.join(out_dir, f"{h}.xla"), "wb") as f:
            f.write(_XLA_MAGIC + len(meta).to_bytes(4, "little")
                    + meta + payload)
        entry["xla"] = f"{h}.xla"
        exported = jax.export.export(jitted,
                                     platforms=list(platforms))(
            param_sds, feed_sds)
        with open(os.path.join(out_dir, f"{h}.shlo"), "wb") as f:
            f.write(exported.serialize())
        entry["shlo"] = f"{h}.shlo"
        if qmeta is not None:
            entry["quant"] = qmeta
        # integrity manifest (the PR-5 checkpoint idiom, for opaque
        # artifact files): CRC32 + size per artifact, verified at
        # Predictor/server load so a torn export names its first bad
        # file instead of surfacing as a raw deserialization traceback
        # — the quant sidecar (weights + scale tables) is covered too,
        # so a quantized artifact is tamper-evident end to end
        entry["integrity"] = {
            name: _file_integrity(os.path.join(out_dir, name))
            for name in ([entry["xla"], entry["shlo"]]
                         + ([qmeta["file"]] if qmeta else []))}
        entries.append(entry)
    index_path = os.path.join(out_dir, AOT_INDEX)
    existing = []
    old = []
    if os.path.exists(index_path):
        try:
            with open(index_path) as f:
                old = json.load(f)
            if not isinstance(old, list):
                old = []
            old = [e for e in old
                   if isinstance(e, dict) and "key" in e]
        except (OSError, ValueError):
            # corrupt index from an interrupted export: re-exporting
            # must self-heal (we lose only this run's stale-artifact
            # GC), not crash on the recovery path
            old = []
    if old:
        # drop superseded buckets AND any entry for a different
        # (stale) program — and unlink their artifact files, or a
        # periodically re-exported serving dir grows without bound
        keep, dropped = [], []
        new_keys = {x["key"] for x in entries}
        for e in old:
            if (e["key"] not in new_keys
                    and e.get("program_hash") == prog_hash):
                keep.append(e)
            else:
                dropped.append(e)
        existing = keep
        # a dropped entry's quant sidecar is shared by every entry of
        # its export — unlink only when no surviving entry references it
        live_files = {n for e in keep + entries
                      for n in (e.get("xla"), e.get("shlo"),
                                (e.get("quant") or {}).get("file"))
                      if n}
        for e in dropped:
            # the sidecar is uniquely named per export, so a same-key
            # re-export does NOT rewrite it in place the way {h}.xla /
            # {h}.shlo are rewritten — the dropped entry's old sidecar
            # must be swept here or a continuous-deploy loop leaks one
            # full-weight npz per publish
            old_q = (e.get("quant") or {}).get("file")
            if old_q and old_q not in live_files:
                try:
                    os.unlink(os.path.join(out_dir, old_q))
                except OSError:
                    pass
            if e["key"] in new_keys:
                continue   # same key: this export just rewrote the files
            for name in (e.get("xla"), e.get("shlo")):
                if name and name not in live_files:
                    try:
                        os.unlink(os.path.join(out_dir, name))
                    except OSError:
                        pass
    # atomic replace: a reader (or a killed exporter) must never see a
    # truncated index. The dir-level model_version is the NEWEST
    # per-entry stamp (kept entries from older exports carry older
    # ones) — the index stays a plain list of bucket entries.
    tmp = f"{index_path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        json.dump(existing + entries, f, indent=1)
    os.replace(tmp, index_path)
    return entries


class Config:
    """AnalysisConfig parity (the knobs that are meaningful on TPU)."""

    def __init__(self, model_dir=None, prog_file=None, params_file=None):
        self.model_dir = model_dir
        self.prog_file = prog_file
        self.params_file = params_file
        self._ir_optim = True
        self._memory_optim = False
        self._device = None          # None → default backend

    def set_model(self, model_dir, params_file=None):
        self.model_dir = model_dir
        self.params_file = params_file

    def switch_ir_optim(self, flag=True):
        self._ir_optim = flag

    def enable_memory_optim(self):
        # XLA owns buffer reuse inside the compiled program — the
        # reference's memory_optimize pass is subsumed; kept as a no-op
        # toggle for API parity (inference/api/analysis_config.cc)
        self._memory_optim = True

    def disable_gpu(self):
        self._device = "cpu"

    def ir_optim(self):
        return self._ir_optim


class ZeroCopyTensor:
    """Input/output handle (AnalysisPredictor::GetInputTensor parity)."""

    def __init__(self, name, owner):
        self.name = name
        self._owner = owner

    def copy_from_cpu(self, arr):
        self._owner._feeds[self.name] = np.asarray(arr)

    def reshape(self, shape):  # parity no-op: shape comes from the array
        pass

    def copy_to_cpu(self):
        out = self._owner._outputs.get(self.name)
        if out is None:
            raise KeyError(f"output {self.name!r} not computed yet; run()")
        return np.asarray(out)




class Predictor:
    """AOT-compiled predictor over a save_inference_model artifact.

    One XLA executable per input-shape signature, cached — the analog of
    AnalysisPredictor's prepared scope + NaiveExecutor, with compilation
    replacing per-op dispatch.

    Trust boundary: the model dir's program (__model__, schema'd JSON)
    and params (.npz) load without executing code. The optional AOT
    fast-path artifacts are different: the portable ``.shlo`` file is
    plain StableHLO, but the platform-native ``.xla`` payload is
    deserialized by jax.experimental.serialize_executable, which
    unpickles internally — load ``.xla`` artifacts only from model
    directories you trust as much as the code itself (our wrapper
    container is structural, the pickle is jax's own layer).

    Thread safety: ``run(feed=...)`` is serialized by a per-predictor
    lock — concurrent callers on ONE predictor get correct (if
    convoyed) results instead of corrupting each other's
    ``_feeds``/``_outputs`` handle state. The SCALING contract is still
    ``clone()``-per-thread (shared weights/executables, private handle
    state, no lock contention); the zero-copy handle flow
    (``get_input_handle`` → ``copy_from_cpu`` → ``run()`` →
    ``copy_to_cpu``) spans multiple calls and is only safe on a
    predictor the thread owns — use clones there. For real QPS use
    ``paddle_tpu.serving.InferenceServer`` (docs/SERVING.md).
    """

    def __init__(self, config):
        self.config = config
        self._run_lock = threading.Lock()
        self._scope = Scope()
        self._exe = Executor(CPUPlace())
        prog, feeds, fetches = static_io.load_inference_model(
            config.model_dir, self._exe,
            model_filename=config.prog_file,
            params_filename=config.params_file, scope=self._scope)
        # AOT index present? Only then hash the program AS SAVED
        # (before any local re-prune — the index was written against
        # exactly that graph); the structural hash walks the whole
        # program, so skip it for the common artifact without AOT
        # exports
        self._aot_idx_path = os.path.join(
            config.model_dir or "", AOT_DIR, AOT_INDEX)
        loaded_hash = (_program_hash(prog)
                       if config.model_dir
                       and os.path.exists(self._aot_idx_path) else None)
        if config.ir_optim():
            # re-prune to the fetch-reachable subgraph (idempotent on
            # save_inference_model artifacts, which prune at save; covers
            # hand-built or stale programs) — shares static/io's pass
            prog = static_io._prune(prog, feeds, fetches)
        self._program = prog
        self._feed_names = feeds
        self._fetch_names = fetches
        self._feeds = {}
        self._outputs = {}
        # AOT artifacts (export_aot): signature key -> index entry;
        # loaded (callable, params) cache per key. Entries for a
        # different program hash are ignored — stale artifacts must
        # never serve an old graph.
        self._aot_index = {}
        self._aot_loaded = {}
        self._prog_hash = loaded_hash
        if loaded_hash is not None:
            try:
                with open(self._aot_idx_path) as f:
                    for e in json.load(f):
                        if e.get("program_hash") == self._prog_hash:
                            self._aot_index[e["key"]] = e
            except Exception:
                # corrupt/unreadable/wrong-shape index: the
                # model+params are fine — degrade to the retrace path
                # like any other AOT artifact failure
                self._aot_index = {}

    # -- AOT path ----------------------------------------------------------
    def _aot_fn(self, feeds):
        """Return a loaded AOT callable for this feed signature, or
        None. Prefers the platform-native executable (no retrace, no
        compile); falls back to the portable StableHLO export (no
        retrace; XLA compiles once); returns None when neither loads
        (wrong platform/version) so the caller re-traces."""
        if not self._aot_index:
            return None
        sig = _sig_of(self._feed_names,
                      {n: feeds[n] for n in self._feed_names})
        h = _sig_key(sig + [["__program__", [], self._prog_hash]])
        if h in self._aot_loaded:
            return self._aot_loaded[h]
        entry = self._aot_index.get(h)
        if entry is None:
            # no negative caching: the probe is one sha256 over the
            # signature, and dynamic shapes would grow the cache
            # unboundedly in a long-lived server
            return None
        import jax
        import jax.export  # not in the jax namespace by default here

        aot_dir = os.path.join(self.config.model_dir, AOT_DIR)
        fn = None
        params = None
        if entry.get("quant"):
            # quantized entries expect int8/bf16 state this fp32
            # Predictor doesn't hold (scale tables live in the sidecar;
            # bf16 weights differ in dtype from the params file) — the
            # single-request path serves fp32 via retrace; the
            # integrity gate below still runs
            params = None
        else:
            try:
                # per-entry params (state_names may differ across
                # entries); any failure — e.g. a stale entry naming a
                # var the scope no longer holds — degrades to the
                # retrace path
                raw = [self._scope.find_var(n)
                       for n in entry["state_names"]]
                if not any(v is None for v in raw):
                    params = tuple(jax.device_put(np.asarray(v))
                                   for v in raw)
            except Exception:
                params = None
        # integrity gate BEFORE any deserialization attempt: CRC/size
        # drift is positive corruption evidence and raises precisely
        # (AOTIntegrityError names the file) — it must NOT be swallowed
        # into the degrade-to-retrace path reserved for wrong
        # platform/version artifacts
        integ = entry.get("integrity", {})
        for name in (entry.get("xla"), entry.get("shlo")):
            if name and name in integ:
                _verify_artifact(os.path.join(aot_dir, name),
                                 integ[name])
        if (params is not None and entry.get("xla")
                and entry["platform"] == jax.devices()[0].platform
                and entry["jax_version"] == jax.__version__):
            try:
                from jax.experimental import serialize_executable as se
                with open(os.path.join(aot_dir, entry["xla"]),
                          "rb") as f:
                    blob = f.read()
                if not blob.startswith(_XLA_MAGIC):
                    raise ValueError("bad .xla container magic")
                off = len(_XLA_MAGIC)
                hlen = int.from_bytes(blob[off:off + 4], "little")
                meta = json.loads(
                    blob[off + 4:off + 4 + hlen].decode("utf-8"))
                payload = blob[off + 4 + hlen:]
                in_tree, out_tree = _aot_treedefs(
                    meta["n_params"], meta["n_feeds"], meta["n_out"])
                fn = se.deserialize_and_load(
                    payload, in_tree, out_tree,
                    execution_devices=jax.devices()[
                        :entry.get("num_devices", 1)])
            except Exception:
                fn = None
        if params is not None and fn is None and entry.get("shlo"):
            try:
                with open(os.path.join(aot_dir, entry["shlo"]),
                          "rb") as f:
                    exported = jax.export.deserialize(f.read())
                # jit the exported call: compile once, then cached —
                # eager exported.call re-traces per request
                fn = jax.jit(exported.call)
            except Exception:
                fn = None
        loaded = None if fn is None else (fn, params)
        self._aot_loaded[h] = loaded
        return loaded

    # -- multi-thread serving (AnalysisPredictor::Clone parity) ------------
    def clone(self):
        """A predictor sharing this one's loaded weights, program,
        executor compile cache and AOT executables, but owning its
        per-request feed/fetch state — the multi-thread serving
        contract (ref: inference/api/analysis_predictor.h:46 Clone:
        'Create a new predictor sharing the weights'). One clone per
        serving thread; run() on different clones is concurrency-safe
        because the shared pieces are read-only after load and XLA
        executable invocation is thread-safe, while the mutable
        request state (_feeds/_outputs and the zero-copy handles bound
        to them) is per-clone."""
        c = object.__new__(Predictor)
        c.__dict__.update(self.__dict__)
        c._feeds = {}
        c._outputs = {}
        c._run_lock = threading.Lock()   # per-clone: clones must not
        return c                         # convoy on the parent's lock

    # -- introspection (AnalysisPredictor::GetInputNames parity) -----------
    def get_input_names(self):
        return list(self._feed_names)

    def get_output_names(self):
        return list(self._fetch_names)

    def get_input_handle(self, name):
        return ZeroCopyTensor(name, self)

    def get_output_handle(self, name):
        return ZeroCopyTensor(name, self)

    # -- execution ----------------------------------------------------------
    def run(self, feed=None):
        """feed: optional {name: array} (else use zero-copy handles).
        Returns outputs in fetch order. Compilation is cached per input
        shape signature by the Executor. Serialized by the predictor's
        lock: concurrent ``run(feed=...)`` calls on one predictor are
        safe (see the class docstring for the clone-per-thread scaling
        contract)."""
        with self._run_lock:
            if feed is not None:
                self._feeds = {k: np.asarray(v) for k, v in feed.items()}
            missing = [n for n in self._feed_names
                       if n not in self._feeds]
            if missing:
                raise KeyError(f"missing inputs: {missing}")
            aot = self._aot_fn(self._feeds)
            if aot is not None:
                fn, params = aot
                outs = fn(params,
                          tuple(self._feeds[n]
                                for n in self._feed_names))
                outs = [np.asarray(o) for o in outs]
            else:
                outs = self._exe.run(self._program,
                                     feed=dict(self._feeds),
                                     fetch_list=list(self._fetch_names),
                                     scope=self._scope)
            self._outputs = dict(zip(self._fetch_names, outs))
            return outs


def create_predictor(config):
    """create_paddle_predictor / CreatePaddlePredictor parity."""
    return Predictor(config)
