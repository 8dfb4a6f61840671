"""The names the train step gives its own parts, as a profile reads them.

``models/bert.py``, ``models/transformer.py``, ``models/olmoe.py`` and
``optimizer.py`` wrap their parts in ``jax.named_scope`` (one vocabulary for
the three models; OLMoE nests four names of its own inside it), the step
functions write two host spans through ``profiler.RecordEvent``, and
``RecordEvent`` is also a ``jax.profiler.TraceAnnotation``. chipbench's
per-layer metrics key on all three; these tests hold them in place on the
CPU. The kernels' names (``name=`` on every ``pallas_call``) are checked
where the TPU compiler is, in ``test_tpu_aot_compile.py``.
"""

import importlib.util
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu import profiler
from paddle_tpu.core import compile_cache
from paddle_tpu.models import bert, kimi_linear, olmoe, transformer
from paddle_tpu.monitor import flight_recorder
from paddle_tpu.parallel.mesh import MeshConfig, make_mesh

MODEL_SCOPES = ("embed", "attention", "attention_core", "ffn", "layer_norm",
                "loss")
#: what a family's program nests inside those (its chipbench configuration
#: lists them under "scopes")
FAMILY_SCOPES = {"bert": (), "transformer": (),
                 "olmoe": ("rope", "moe_router", "moe_dispatch",
                           "moe_experts"),
                 "kimi_linear": ("kda_core", "short_conv", "kda_gate",
                                 "mla_expand", "moe_router", "moe_dispatch",
                                 "moe_experts", "moe_shared")}


def _tiny(family):
    """(step_fn, params, opt_state, host batch) of a tiny trainer on one
    CPU device."""
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    opt = pt.optimizer.Adam(1e-3)
    if family == "bert":
        cfg = bert.bert_tiny()
        init_fn, step_fn = bert.make_train_step(cfg, opt, mesh)
        batch = bert.synthetic_batch(cfg, 4, 16, max_preds=4)
    elif family == "olmoe":
        cfg = olmoe.olmoe_tiny()
        init_fn, step_fn = olmoe.make_train_step(cfg, opt, mesh)
        batch = olmoe.synthetic_batch(cfg, 4, 16)
    elif family == "kimi_linear":
        cfg = kimi_linear.kimi_linear_tiny(experts_held=(4, 4))
        init_fn, step_fn = kimi_linear.make_train_step(cfg, opt, mesh)
        batch = kimi_linear.synthetic_batch(cfg, 2, 24)
    else:
        cfg = transformer.transformer_tiny()
        init_fn, step_fn = transformer.make_train_step(cfg, opt, mesh)
        batch = transformer.synthetic_batch(cfg, 4, 8, 8)
    params, opt_state = init_fn(jax.random.PRNGKey(0))
    return step_fn, params, opt_state, batch


@pytest.mark.parametrize("family", ["bert", "transformer", "olmoe",
                                    "kimi_linear"])
def test_lowered_step_names_every_scope_forward_and_backward(family):
    """Each model scope is on a name stack under ``jvp(`` (forward) and on one
    under ``transpose(jvp(`` (backward); ``optimizer`` is under neither. jax
    wraps only the outermost scope, so ``layer_norm`` inside ``embed`` shows
    as ``jvp(embed)/layer_norm``."""
    step_fn, params, opt_state, batch = _tiny(family)
    text = step_fn.jitted.lower(params, opt_state,
                                step_fn.place(batch)).as_text(debug_info=True)
    stacks = set(re.findall(r'loc\("(jit\(step\)/[^"]*)"', text))
    assert stacks

    def on_a_stack(scope, under):
        return any(s.startswith(f"jit(step)/{under}")
                   and re.search(rf"[/(]{scope}[/)]", s) for s in stacks)

    for scope in MODEL_SCOPES + FAMILY_SCOPES[family]:
        assert on_a_stack(scope, "jvp("), (scope, "forward")
        assert on_a_stack(scope, "transpose(jvp("), (scope, "backward")
    assert any(s.startswith("jit(step)/optimizer/") for s in stacks)
    assert not any("optimizer" in s for s in stacks
                   if "jvp(" in s or "transpose(" in s)
    assert not any(scope in s for s in stacks
                   if s.startswith("jit(step)/optimizer/")
                   for scope in MODEL_SCOPES)


def test_transformer_step_hands_out_its_jit_and_its_placement():
    """As bert's does; and the closure still holds exactly one object with
    ``.lower``, which chipbench's runner takes the jit from."""
    step_fn, params, opt_state, batch = _tiny("transformer")
    assert hasattr(step_fn.jitted, "lower")
    placed = step_fn.place(batch)
    assert set(placed) == set(batch)
    assert all(isinstance(v, jax.Array) for v in placed.values())
    lowerable = [c.cell_contents for c in step_fn.__closure__
                 if hasattr(c.cell_contents, "lower")]
    assert lowerable == [step_fn.jitted]


def _host_events(trace_dir):
    """[(name, start_ns, end_ns)] of the /host:CPU plane of the one trace
    under ``trace_dir``."""
    path, = sorted(trace_dir.rglob("*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events]


@pytest.mark.parametrize("family", ["bert", "transformer", "olmoe",
                                    "kimi_linear"])
def test_step_fn_writes_its_two_spans_into_a_jax_profile(family, tmp_path):
    """Two steps under ``jax.profiler``: ``trainer/place`` and
    ``trainer/enqueue`` are on the host plane, twice each, one after the
    other and inside no other span of the program's."""
    step_fn, params, opt_state, batch = _tiny(family)
    loss, params, opt_state = step_fn(params, opt_state, batch)   # compile
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for _ in range(2):
            loss, params, opt_state = step_fn(params, opt_state, batch)
        float(loss)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    program = sorted((e for e in events if e[0].startswith("trainer/")),
                     key=lambda e: e[1])
    assert [e[0] for e in program] == ["trainer/place",
                                       "trainer/enqueue"] * 2
    for earlier, later in zip(program, program[1:]):
        assert earlier[2] <= later[1]          # in turn, none inside another


def test_record_event_is_an_annotation_and_still_feeds_ring_and_recorder(
        tmp_path):
    """Under a jax profile the span is on the host plane; with the profiler
    on it is in the ring with its args; with the flight recorder armed it is
    in flight while open and noted when closed. With none of the three it
    does nothing one can see, and does not fail."""
    with profiler.RecordEvent("quiet"):
        pass
    profiler.reset_profiler()
    profiler.start_profiler(trace_dir=str(tmp_path))
    flight_recorder.enable()
    try:
        with profiler.RecordEvent("outer/span", args={"flow": 7}):
            assert [s["name"] for s in flight_recorder.RECORDER.in_flight()
                    ] == ["outer/span"]
            with profiler.RecordEvent("inner/span"):
                pass
    finally:
        flight_recorder.disable()
        profiler.stop_profiler()
    ring = {name: args for name, _, _, _, args in profiler._events.snapshot()}
    assert ring == {"outer/span": {"flow": 7}, "inner/span": None}
    assert flight_recorder.RECORDER.in_flight() == []
    noted = [e["name"] for e in flight_recorder.RECORDER.events()
             if e["kind"] == "span"]
    assert noted[-2:] == ["inner/span", "outer/span"]
    on_plane = {e[0]: e for e in _host_events(tmp_path)}
    outer, inner = on_plane["outer/span"], on_plane["inner/span"]
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert "quiet" not in on_plane
    profiler.reset_profiler()


@pytest.mark.parametrize("names_in_key", [False, True],
                         ids=["jax_default_is_stale", "enable_is_not"])
def test_a_cached_executable_keeps_the_names_it_was_compiled_with(
        names_in_key, tmp_path, monkeypatch):
    """The evidence behind ``compile_cache.enable()`` setting
    ``jax_compilation_cache_include_metadata_in_key``. A function is
    compiled through the persistent cache, then the same function with one
    more ``named_scope``. With jax's default key (debug info stripped before
    hashing) the second is a HIT and the executable it gets lacks the scope:
    a profile of it would show the old names. With the names in the key, as
    ``enable()`` leaves it, the second compiles and has its scope."""
    def body(x):
        return jnp.sin(x) * 2.0 + 1.0

    def plain(x):
        return body(x)

    def scoped(x):
        with jax.named_scope("attention"):
            return body(x)
    scoped.__name__ = scoped.__qualname__ = "plain"   # one module name

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    monkeypatch.setattr(compile_cache, "DEFAULT_DIR", str(tmp_path / "cache"))
    x = jnp.ones((8, 8))
    was = jax.config.jax_compilation_cache_include_metadata_in_key
    compile_cache.enable()
    try:
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          names_in_key)
        compile_cache.reset_stats()
        first = jax.jit(plain).lower(x).compile()
        assert compile_cache.stats()["misses"] == 1
        second = jax.jit(scoped).lower(x).compile()
        assert "attention" not in first.as_text()
        if names_in_key:
            assert compile_cache.stats()["hits"] == 0
            assert "attention" in second.as_text()
        else:
            assert compile_cache.stats()["hits"] == 1
            assert "attention" not in second.as_text()
    finally:
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          was)
        compile_cache.disable()


def test_trace_summary_prints_a_recorded_step_by_scope_category_and_kernel(
        capsys):
    """``tools/trace_summary.py`` on the small trace recorded on a v5e from
    the tree that brought the scopes (chipbench's fixture)."""
    root = pathlib.Path(__file__).resolve().parent.parent
    spec = importlib.util.spec_from_file_location(
        "trace_summary", root / "tools" / "trace_summary.py")
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    trace = (root / "chipbench" / "tests" / "fixtures" / "traces"
             / "bert_toy.mlm_toy.scopes.xplane.pb.gz")
    tool.main([str(trace), "--top", "4"])
    out = capsys.readouterr().out
    for scope in MODEL_SCOPES + ("optimizer", "unscoped", "[forward]",
                                 "[backward]"):
        assert re.search(rf"^{re.escape(scope)} +[0-9. ]+$", out, re.M), scope
    assert "kernel fused_adam" in out and "kernel layer_norm_fwd" in out
    assert "== device time a step by HLO category ==" in out
    assert "host trainer/place: median" in out
    assert "the four directions sum to" in out
    with pytest.raises(SystemExit):
        tool.main([str(root / "docs")])          # no trace under it
