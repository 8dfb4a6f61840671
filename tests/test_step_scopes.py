"""The names the train step gives its own parts, as a profile reads them.

``models/bert.py``, ``models/transformer.py``, ``lm_trainer.Decoder``'s
models and ``optimizer.py`` wrap their parts in ``jax.named_scope`` (one
vocabulary for all of them; a decoder nests names of its own inside it, and
``parallel/moe.py`` seven stage names inside ``moe_router`` and
``moe_dispatch``), the step
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
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import profiler
from paddle_tpu.core import compile_cache
from paddle_tpu.models import bert, kimi_linear, olmoe, transformer
from paddle_tpu.monitor import flight_recorder
from paddle_tpu.parallel import moe
from paddle_tpu.parallel.mesh import MeshConfig, make_mesh

MODEL_SCOPES = ("embed", "attention", "attention_core", "ffn", "layer_norm",
                "loss")
#: the stage scopes of ``parallel/moe.py``: every operation under
#: ``moe_router`` or ``moe_dispatch`` is under exactly one of them
ROUTER_STAGES = ("router_logits", "router_scores", "router_select",
                 "router_stats")
DISPATCH_STAGES = ("dispatch_order", "dispatch_gather", "dispatch_combine")
#: what a family's program nests inside those (its chipbench configuration
#: lists them under "scopes") and, beside them, the expert layer's stages
#: that have a backward in that step: OLMoE sorts integers once, outside the
#: gradient; Kimi Linear's loss has no balance or z term
FAMILY_SCOPES = {"bert": (), "transformer": (),
                 "olmoe": ("rope", "moe_router", "moe_dispatch",
                           "moe_experts", *ROUTER_STAGES,
                           *DISPATCH_STAGES[1:]),
                 "kimi_linear": ("kda_core", "short_conv", "kda_gate",
                                 "mla_expand", "moe_router", "moe_dispatch",
                                 "moe_experts", "moe_shared",
                                 *ROUTER_STAGES[:3], *DISPATCH_STAGES)}


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


def _expert_params(held, gated, latent):
    """Parameters of one tiny expert layer: a router 8 wide on a hidden of
    16, the held experts' stacks (all 8 where ``held`` is None), a selection
    bias, a latent's projections where asked."""
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 8))
    n, width = (8 if held is None else held[1]), latent or 16

    def normal(*shape):
        return 0.1 * jax.random.normal(next(keys), shape)

    params = {"router_w": normal(16, 8), "w_up": normal(n, width, 32),
              "w_down": normal(n, 32, width)}
    if gated:
        params["w_gate"] = normal(n, width, 32)
    if latent:
        params.update(latent_down=normal(16, latent),
                      latent_up=normal(latent, 16))
    params["router_bias"] = jnp.zeros((8,))
    return params


EXPERT_LAYERS = {"all-gated": (None, True, None),
                 "all-plain": (None, False, None),
                 "all-gated-latent": (None, True, 8),
                 "held-gated": ((2, 4), True, None),
                 "held-plain": ((2, 4), False, None),
                 "held-plain-latent": ((2, 4), False, 8)}


def _lowered_expert_layer(held, gated, latent):
    """The lowered value-and-grad of ``dropless_moe_ffn`` inside ``ffn``,
    with every operation's name stack."""
    scoring = moe.Scoring() if held is None else moe.Scoring(
        "sigmoid", renormalize=True, scale=2.5)

    def loss(params, x):
        with jax.named_scope("ffn"):
            y, aux = moe.dropless_moe_ffn(
                params, x, 2, scoring=scoring, held=held,
                activation="silu" if gated else "relu2")
        return jnp.sum(y) + aux["balance"] + aux["z"]

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        _expert_params(held, gated, latent),
        jnp.ones((2, 12, 16))).as_text(debug_info=True)


def _stacks_of(text, *operations):
    """[(operation, name stack)] of the ``stablehlo`` operations of those
    names that the lowered text's public function runs. An operation with a
    region (a scatter's update, a sort's order) carries its location where
    the region closes; one inside a private function (``jnp.take``,
    ``jnp.bincount`` and their like are functions of their own) runs under
    the stack of every call that reaches it, with its own behind it."""
    named = dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))
    lines = text.split("\n")
    inside, public, function = {}, None, None
    for i, line in enumerate(lines):
        head = re.match(r"  func\.func (public|private) @([\w.]+)\(", line)
        if head:
            function = head.group(2)
            inside[function] = []
            public = function if head.group(1) == "public" else public
            continue
        op = re.search(r'= "?(?:stablehlo\.(\w+)"?|call @([\w.]+)\()', line)
        if not op or not (op.group(2) or op.group(1) in operations):
            continue
        if line.rstrip().endswith("({"):
            close = line[:len(line) - len(line.lstrip())] + "})"
            line = next(l for l in lines[i + 1:] if l.startswith(close))
        at = re.search(r"loc\((#loc\d+)\)\s*$", line)
        inside[function].append((op.group(1), op.group(2),
                                 named.get(at.group(1), "") if at else ""))

    def reached(function, under):
        for operation, callee, stack in inside[function]:
            stack = f"{under}/{stack}" if under else stack
            if callee:
                yield from reached(callee, stack)
            else:
                yield operation, stack

    return list(reached(public, ""))


@pytest.mark.parametrize("held,gated,latent", EXPERT_LAYERS.values(),
                         ids=EXPERT_LAYERS)
def test_every_router_and_dispatch_operation_is_under_one_stage(
        held, gated, latent):
    """The lowered value-and-grad of ``dropless_moe_ffn`` inside ``ffn``:
    a name stack that holds ``moe_router`` holds exactly one router stage
    behind it, one that holds ``moe_dispatch`` exactly one dispatch stage,
    and no stage is anywhere else. Each stage is on a forward and on a
    backward stack (``dispatch_order`` has no backward where nothing is
    held: sorts of integers; where a share is held the backward's loop
    enters it again). ``_held_pass``'s operations (the one ``cumsum`` of
    the layer among them) are under ``moe_dispatch/dispatch_order``."""
    text = _lowered_expert_layer(held, gated, latent)
    stacks = [re.split(r"[/()]", s)
              for s in set(re.findall(r'loc\("(jit\(loss\)/[^"]*)"', text))]
    seen = set()
    for names in stacks:
        stages = [n for n in names if n in ROUTER_STAGES + DISPATCH_STAGES]
        outer = [n for n in names if n in ("moe_router", "moe_dispatch")]
        if not outer:
            assert not stages, names
            continue
        assert len(outer) == 1 and len(stages) == 1, names
        assert stages[0] in (ROUTER_STAGES if outer == ["moe_router"]
                             else DISPATCH_STAGES), names
        assert names.index(stages[0]) == names.index(outer[0]) + 1, names
        seen.add((stages[0], "transpose" in names))
    both = set(ROUTER_STAGES + DISPATCH_STAGES)
    assert {s for s, backward in seen if not backward} == both
    assert {s for s, backward in seen if backward} == (
        both - {"dispatch_order"} if held is None else both)
    passes = [names for names in stacks if "cumsum" in names]
    assert bool(passes) == (held is not None)
    assert all("dispatch_order" in names for names in passes)


@pytest.mark.parametrize("program", [*EXPERT_LAYERS, "olmoe",
                                     "kimi_linear"])
def test_selection_and_counts_gather_and_scatter_nothing(program):
    """On the six expert layers and on the two tiny steps: no
    ``stablehlo.gather`` and no ``stablehlo.scatter`` is under
    ``router_select`` forward (the chosen scores come out of the selection's
    own sort: ``moe._biased_top_k``; backward, ``lax.top_k``'s own rule
    scatters where a router adds no bias) or under ``router_stats`` (the
    counts are a compare and a sum: ``moe._counts``); XLA runs either a
    scalar at a time on the chip. The steps still gather and scatter
    elsewhere, so the reading finds what it looks for."""
    if program in EXPERT_LAYERS:
        text = _lowered_expert_layer(*EXPERT_LAYERS[program])
    else:
        step_fn, params, opt_state, batch = _tiny(program)
        text = step_fn.jitted.lower(
            params, opt_state, step_fn.place(batch)).as_text(debug_info=True)
    moved = _stacks_of(text, "gather", "scatter")
    assert moved and all("/" in stack for _, stack in moved)
    for operation, stack in moved:
        names = re.split(r"[/()]", stack)
        assert "router_stats" not in names, (operation, stack)
        assert "router_select" not in names or "transpose" in names, (
            operation, stack)
    sorts = [re.split(r"[/()]", stack)
             for _, stack in _stacks_of(text, "sort")]
    if program != "olmoe":         # a selection bias: the sort carries scores
        assert any("router_select" in names for names in sorts)


def test_the_capacity_layer_gets_the_router_s_first_three_stages():
    """``moe_ffn`` calls ``route`` too: logits, scores and selection carry
    their stage names there, with no ``moe_router`` around them."""
    cfg = moe.MoEConfig(d_model=16, d_hidden=32, num_experts=4)
    params = moe.init_moe_params(jax.random.PRNGKey(0), cfg)
    text = jax.jit(lambda p, x: moe.moe_ffn(p, cfg, x)).lower(
        params, jnp.ones((2, 8, 16))).as_text(debug_info=True)
    for stage in ROUTER_STAGES[:3]:
        assert re.search(rf'loc\("jit\([^"]*/{stage}/', text), stage
    assert "moe_router" not in text and "router_stats" not in text


@pytest.mark.parametrize("array", [np.asarray, jnp.asarray],
                         ids=["numpy", "jax"])
@pytest.mark.parametrize("rows", [0, 1, 8, 9],
                         ids=["none", "one-row", "a-tile", "a-tile-and-one"])
def test_held_passes_is_the_trip_count_of_the_loop(rows, array, monkeypatch):
    """``moe.held_passes`` on a NumPy and on a jax value against the passes
    ``_held_experts`` runs, forward and backward, on a layer that holds
    ``rows`` assignments with a tile of 8: each entry of ``_held_pass``
    is counted on the host as the loops run."""
    tile, top_k, first, n = 8, 2, 2, 4
    entered = []
    held_pass = moe._held_pass

    def counted(i, *args):
        jax.debug.callback(lambda i: entered.append(int(i)), i)
        return held_pass(i, *args)

    monkeypatch.setattr(moe, "_held_pass", counted)
    # 12 tokens, two choices each: the first ``rows`` assignments fall on
    # the held experts 2 to 5 in turn, the others on expert 0
    top_e = np.zeros(12 * top_k, np.int32)
    top_e[:rows] = first + np.arange(rows) % n
    top_e = top_e.reshape(12, top_k)
    key = np.where((top_e >= first) & (top_e < first + n), top_e - first, n)
    order = np.argsort(key.reshape(-1), kind="stable")
    order = np.pad(order, (0, -order.size % tile))
    sizes = np.bincount(top_e.reshape(-1), minlength=8)[first:first + n]
    params = _expert_params((first, n), True, None)
    weights = tuple(params[w] for w in ("w_gate", "w_up", "w_down"))

    def total(xt, top_p):
        return jnp.sum(moe._held_experts(
            xt, top_p, weights, jnp.asarray(order, jnp.int32),
            jnp.asarray(sizes, jnp.int32), top_k, None, tile, "silu"))

    want = moe.held_passes(array(np.int32(rows)), tile)
    assert int(want) == (rows + tile - 1) // tile
    jax.block_until_ready(jax.grad(total, argnums=(0, 1))(
        jnp.ones((12, 16)), jnp.full((12, top_k), 0.5)))
    jax.effects_barrier()
    # the forward loop and the backward's, ``want`` passes each
    assert sorted(entered) == sorted(2 * list(range(int(want))))


def test_held_passes_takes_a_layer_s_rows_at_once():
    """One number an expert layer, as a reader of ``step_fn.aux`` has them:
    at par (one pass), a share past a pass's rows (two), nothing held."""
    rows = np.array([16384, 34000, 0])
    assert moe.held_passes(rows, 32768).tolist() == [1, 2, 0]
    assert moe.held_passes(jnp.asarray(rows), 32768).tolist() == [1, 2, 0]


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
