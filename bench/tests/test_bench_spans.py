"""The program's own marks read from a profiler trace: the serve.* spans
and the gap split they give, model scopes from compiled HLO and the device
time by scope, on small synthetic traces; then, on the CPU at a tiny
width, the engine's spans in a real trace, the scopes in the packed step's
HLO, and the engine's work counters against the step inputs."""
import json
import shutil

import numpy as np
import pytest

from bench import model as BM
from bench import registry
from bench import spans as S
from bench import trace as T
from bench.traffic import generator as G

MS = 1_000_000
SEED = 2 ** 31 + 11


def _is_step(name):
    return "packed" in name


def _trace():
    # host: three engine steps inside bench.step spans, client work between
    # them, one wait for an arrival; device: three step programs
    spans = [("bench.step", 0, 10 * MS), ("bench.client", 10 * MS, 12 * MS),
             ("bench.step", 12 * MS, 22 * MS),
             ("bench.wait_arrival", 22 * MS, 30 * MS),
             ("bench.step", 30 * MS, 40 * MS)]
    modules = [("jit__packed(11)", 1 * MS, 8 * MS),
               ("jit__packed(11)", 13 * MS, 20 * MS),
               ("jit__packed(11)", 31 * MS, 38 * MS)]
    ops = [("%fusion.1 = f32[8] fusion(x)", 1 * MS, 5 * MS),
           ("%dot.2 = f32[8] dot(x, y)", 5 * MS, 8 * MS),
           ("%argmax.3 = s32[4] reduce(x)", 9 * MS, 10 * MS),
           ("%fusion.1 = f32[8] fusion(x)", 13 * MS, 20 * MS),
           ("%fusion.1 = f32[8] fusion(x)", 31 * MS, 38 * MS)]
    serve = [("serve.step", 0, 10 * MS), ("serve.schedule", 0, 1 * MS),
             ("serve.device", 1 * MS, 2 * MS),
             ("serve.sample", 2 * MS, 9 * MS),
             ("serve.register", 8 * MS + MS // 2, 9 * MS),
             ("serve.step", 12 * MS, 22 * MS),
             ("serve.admit", 12 * MS, 12 * MS + MS // 2),
             ("serve.schedule", 12 * MS + MS // 2, 13 * MS),
             ("serve.sample", 13 * MS, 21 * MS),
             ("serve.step", 30 * MS, 40 * MS),
             ("serve.schedule", 30 * MS, 31 * MS),
             ("serve.sample", 31 * MS, 39 * MS)]
    return T.Trace(ops=ops, modules=modules, spans=spans, devices=1), serve


def test_scope_of_takes_the_innermost_model_scope():
    assert S.scope_of("jit(_packed)/while/body/closed_call/attn.core/dot") \
        == "attn.core"
    assert S.scope_of("jit(f)/mlp/attn.proj/add") == "attn.proj"
    assert S.scope_of("jit(_packed)/while/body/dynamic_slice") == "unscoped"


def test_hlo_scopes_read_op_name_metadata():
    text = "\n".join([
        'HloModule jit__packed',
        '  %fusion.185 = bf16[16,8]{1,0} fusion(%p), kind=kLoop, '
        'metadata={op_name="jit(_packed)/while/body/closed_call/attn.core/'
        'dot_general" stack_frame_id=6}',
        '  ROOT %copy.98 = bf16[16]{0} copy(%x), '
        'metadata={op_name="jit(_packed)/while/body/attn.gather/gather"}',
        '  %param.1 = f32[] parameter(0)',
        # a layout copy the compiler inserted reads a scoped value through
        # a bare bitcast: both take that scope; a copy of a parameter or
        # of the layer scan's own slice stays unscoped
        '  %copy.99 = bf16[8,16]{1,0} copy(%bitcast.269), backend_config={}',
        '  %bitcast.269 = bf16[8,16]{0,1} bitcast(%fusion.185)',
        '  %copy.7 = f32[] copy(%param.1)',
        '  %copy.95 = bf16[1,8] copy(%slice.3), '
        'metadata={op_name="jit(_packed)/while/body/dynamic_slice"}',
        '  %slice.3 = bf16[1,8] slice(%fusion.185)'])
    assert S.hlo_scopes(text) == {("fusion.185", "bf16[16,8]"): "attn.core",
                                  ("copy.98", "bf16[16]"): "attn.gather",
                                  ("param.1", "f32[]"): "unscoped",
                                  ("copy.99", "bf16[8,16]"): "attn.core",
                                  ("bitcast.269", "bf16[8,16]"): "attn.core",
                                  ("copy.7", "f32[]"): "unscoped",
                                  ("copy.95", "bf16[1,8]"): "unscoped",
                                  ("slice.3", "bf16[1,8]"): "attn.core"}


def test_instr_key_reads_name_and_result_type_without_layouts():
    # as a TPU op event names an instruction, and as compiled text does
    assert S.instr_key("%fusion.185 = (f32[16,8,4,128]{3,2,1,0:T(8,128)}, "
                       "bf16[16,8,4,128,512]{4,3,2,1,0:T(8,128)(2,1)}) "
                       "fusion(f32[8,4]{1,0} %reshape.517)") == \
        ("fusion.185", "(f32[16,8,4,128], bf16[16,8,4,128,512])")
    assert S.instr_key("%copy.98 = bf16[16,128]{1,0:T(8,128)(2,1)} "
                       "copy(bf16[16,128] %bitcast.268)") == \
        ("copy.98", "bf16[16,128]")
    assert S.result_type(" s32[] parameter(0)") == "s32[]"


def test_op_scopes_pick_each_programs_table():
    tr, _ = _trace()
    a = {("fusion.1", "f32[8]"): "attn.core", ("dot.2", "f32[8]"): "mlp"}
    b = {("dot.2", "f32[8]"): "head", ("copy.5", "f32[8]"): "attn.gather"}
    # the first module's two names are both known to `a`, one to `b`; the
    # later modules' one name only to `a`; the argmax runs outside every
    # module; a table of another module name is never read
    assert S.op_scopes(tr, [("jit__packed", b), ("jit__packed", a),
                            ("jit__argmax", b)]) == [
        "attn.core", "mlp", "unscoped", "attn.core", "attn.core"]
    # the executions of one program read one table: program 11's names
    # are fusion.1 and dot.2, of which `b` knows one
    assert S.op_scopes(tr, [("jit__packed", b)]) == [
        "unscoped", "head", "unscoped", "unscoped", "unscoped"]
    assert S.op_scopes(tr, [("jit__argmax", a)]) == ["unscoped"] * 5
    assert S.hlo_module_name(
        "HloModule jit__packed, is_scheduled=true, entry_computation_"
        "layout={(f32[8]{0})->f32[]}\n") == "jit__packed"


def _shared_names_trace():
    """Two step programs of one model code at two widths: the same
    instruction names with other shapes; the first runs twice. Then an
    argmax program's fusion of the same name."""
    ops = [("%fusion.1 = f32[8] fusion(x)", 1 * MS, 2 * MS),
           ("%copy.2 = f32[8] copy(x)", 2 * MS, 3 * MS),
           ("%fusion.1 = f32[4] fusion(x)", 5 * MS, 6 * MS),
           ("%copy.2 = f32[4] copy(x)", 6 * MS, 7 * MS),
           ("%fusion.1 = f32[8] fusion(x)", 8 * MS, 9 * MS),
           ("%fusion.1 = s32[4] fusion(x)", 10 * MS, 11 * MS)]
    modules = [("jit__packed(101)", 1 * MS, 3 * MS),
               ("jit__packed(202)", 5 * MS, 7 * MS),
               ("jit__packed(101)", 8 * MS, 9 * MS),
               ("jit__argmax(303)", 10 * MS, 11 * MS)]
    return T.Trace(ops=ops, modules=modules, spans=[], devices=1)


def test_op_scopes_tell_programs_that_share_names_apart():
    tr = _shared_names_trace()
    wide = {("fusion.1", "f32[8]"): "attn.core",
            ("copy.2", "f32[8]"): "attn.gather"}
    narrow = {("fusion.1", "f32[4]"): "mlp",
              ("copy.2", "f32[4]"): "attn.kv_write"}
    want = ["attn.core", "attn.gather", "mlp", "attn.kv_write", "attn.core",
            "unscoped"]
    # each program reads its own table, in either order of the tables;
    # the argmax program reads no step table
    for programs in ([("jit__packed", wide), ("jit__packed", narrow)],
                     [("jit__packed", narrow), ("jit__packed", wide)]):
        assert S.op_scopes(tr, programs) == want


def test_op_scopes_refuse_to_guess_between_tied_tables():
    tr = _shared_names_trace()
    wide = {("fusion.1", "f32[8]"): "attn.core",
            ("copy.2", "f32[8]"): "attn.gather"}
    other = {("fusion.1", "f32[8]"): "attn.core",
             ("copy.2", "f32[8]"): "attn.kv_write"}
    # two tables know both instructions of program 101 and disagree on
    # copy.2: no table is chosen, in either order
    for programs in ([("jit__packed", wide), ("jit__packed", other)],
                     [("jit__packed", other), ("jit__packed", wide)]):
        with pytest.raises(ValueError, match=r"jit__packed\(101\)"):
            S.op_scopes(tr, programs)
    # tables that agree on every instruction a program ran scope it alike
    assert S.op_scopes(tr, [("jit__packed", wide),
                            ("jit__packed", dict(wide))])[:2] == [
        "attn.core", "attn.gather"]
    # a table that knows more of a program's instructions wins outright
    assert S.op_scopes(tr, [("jit__packed",
                             {("fusion.1", "f32[8]"): "mlp"}),
                            ("jit__packed", other)])[:2] == [
        "attn.core", "attn.kv_write"]


def test_scope_seconds_partition_busy_time():
    tr, _ = _trace()
    scopes = S.op_scopes(tr, [("jit__packed",
                              {("fusion.1", "f32[8]"): "attn.core",
                               ("dot.2", "f32[8]"): "mlp"})])
    secs = S.scope_seconds(tr, scopes)
    assert secs == pytest.approx({"attn.core": 0.018, "mlp": 0.003,
                                  "unscoped": 0.001})
    # busy is 22 ms (bench/tests/test_bench_trace.py), all of it scoped
    # or unscoped
    assert sum(secs.values()) == pytest.approx(T.busy_ns(tr) / 1e9)


def test_innermost_pieces():
    spans = [("serve.step", 0, 10), ("serve.sample", 2, 9),
             ("serve.register", 5, 6)]
    assert S.innermost(spans, 1, 12) == [
        (1, 2, "serve.step"), (2, 5, "serve.sample"),
        (5, 6, "serve.register"), (6, 9, "serve.sample"),
        (9, 10, "serve.step"), (10, 12, None)]


def test_gap_split_partitions_the_host_gap_exactly():
    tr, serve = _trace()
    split = S.gap_split_ns(tr, serve, _is_step)
    gaps = T.step_gaps_ns(tr, _is_step)
    # one gap, 8 -> 13 ms, holding the 1 ms argmax (9-10): idle 8-9 under
    # serve.sample (0.5 ms) and serve.register (0.5 ms), 10-12 under
    # bench.client only, 12-13 under serve.admit then serve.schedule
    assert split == {"gaps": 1, "prepare": 1 * MS, "sample": 1 * MS,
                     "other": 2 * MS}
    assert split["prepare"] + split["sample"] + split["other"] == \
        sum(gaps) / len(gaps)


def test_gap_split_matches_host_gap_on_many_gaps():
    rng = np.random.default_rng(0)
    t, modules, ops, spans, serve = 0, [], [], [], []
    for _ in range(30):
        a, b, c, d = (int(x) for x in rng.integers(1, 50, 4) * 1000)
        serve += [("serve.step", t, t + a + b + 100_000),
                  ("serve.schedule", t, t + a),
                  ("serve.device", t + a, t + a + b)]
        spans.append(("bench.step", t, t + a + b + 100_000 + c))
        s = t + a + b // 2
        modules.append(("jit__packed", s, s + 100_000))
        ops.append(("%fusion.1 = f32[8] fusion(x)", s, s + 100_000))
        spans.append(("bench.client", t + a + b + 100_000 + c,
                      t + a + b + 100_000 + c + d))
        t += a + b + 100_000 + c + d
    tr = T.Trace(ops=ops, modules=modules, spans=spans, devices=1)
    split = S.gap_split_ns(tr, serve, _is_step)
    gaps = T.step_gaps_ns(tr, _is_step)
    assert split["gaps"] == len(gaps) == 29
    assert split["prepare"] + split["sample"] + split["other"] == \
        pytest.approx(sum(gaps) / len(gaps), abs=1e-6)
    assert split["sample"] == 0 and split["prepare"] > 0


def test_named_gaps_use_serve_spans_and_fall_back():
    tr, serve = _trace()
    named = S.named_gaps(tr, serve)
    # as bench.trace.top_gaps, longest first; a gap more than half under
    # one serve span takes the innermost such span's name (8-9 ms: sample,
    # whose register child covers only half), the rest keep the harness's
    assert named == [["bench.wait_arrival", 0.011], ["bench.client", 0.003],
                     ["serve.step", 0.002], ["serve.schedule", 0.001],
                     ["serve.sample", 0.001]]


def _tiny_config(**serving):
    c = registry.config("granite-3-2b")
    c["name"] = "tiny"
    c["model"].update(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
                      head_dim=16, d_ff=128, vocab_size=256)
    c["serving"].update(serving)
    return c


def _engine(c, seed=SEED):
    from repro.serve import PagedEngine
    arch = registry.arch(c["arch"])
    cfg = arch.model_config(c)
    params = BM.make_params(c, seed, cfg, arch)
    sv = c["serving"]
    return PagedEngine(params, cfg, max_batch=sv["max_batch"],
                       max_len=sv["max_len"], block_size=sv["block_size"],
                       num_blocks=sv["num_blocks"],
                       prefix_sharing=sv["prefix_sharing"],
                       decode_sharing=False, packed=sv["packed"],
                       speculative=sv["speculative"],
                       async_loop=sv["async_loop"], admission=None)


def _requests(n, lo, hi, new, rng):
    from repro.serve import Request
    return [Request(uid=i, prompt=rng.integers(0, 256, int(rng.integers(
        lo, hi))).astype(np.int32), max_new_tokens=new) for i in range(n)]


def test_engine_spans_nest_in_each_bench_step(tmp_path):
    """A tiny PagedEngine served under jax.profiler.trace: every bench.step
    holds one serve.step, and the schedule, device and sample phases lie
    inside it."""
    import jax
    eng = _engine(_tiny_config(max_batch=4, max_len=128, block_size=8,
                               num_blocks=80))
    for r in _requests(3, 8, 30, 3, np.random.default_rng(0)):
        eng.submit(r)
    eng.step()                           # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        while eng.busy:
            with jax.profiler.TraceAnnotation("bench.step"):
                eng.step()
    tr = T.load(tmp_path)
    serve = S.load_serve_spans(tmp_path)
    steps = [(s, e) for n, s, e in tr.spans if n == "bench.step"]
    assert len(steps) >= 3
    for s, e in steps:
        inside = [n for n, a, b in serve if s <= a and b <= e]
        assert inside.count("serve.step") == 1
        assert {"serve.admit", "serve.schedule", "serve.device",
                "serve.sample"} <= set(inside)
    assert all(any(s <= a and b <= e for s, e in steps)
               for _, a, b in serve)


def _recording(eng):
    """Wrap the packed step like the harness does; returns the list the
    step inputs land in."""
    seen, fn = [], eng._packed_fn

    def recorded(*a):
        seen.append(a)
        return fn(*a)
    eng._packed_fn = recorded
    return seen, fn


def test_packed_step_hlo_carries_the_model_scopes():
    c = _tiny_config(max_batch=4, max_len=128, block_size=8, num_blocks=80)
    eng = _engine(c)
    seen, fn = _recording(eng)
    for r in _requests(2, 8, 20, 2, np.random.default_rng(1)):
        eng.submit(r)
    eng.run()
    from bench import traced
    name, table = traced.compiled_scopes(fn, seen[0])
    assert name == "jit__packed"
    assert {"attn.proj", "attn.kv_write", "attn.gather", "attn.core", "mlp",
            "head"} <= set(table.values())


def test_compiled_scopes_bypass_a_cache_filled_without_scopes(tmp_path):
    """A persistent compilation cache filled by code without the model's
    scopes hands that program back to the scoped code, since its key leaves
    out op_name; the scope table is read from a fresh compile."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    from bench import traced

    def step(scoped):
        def _packed(x):
            if scoped:
                with jax.named_scope("mlp"):
                    return jnp.sin(x) * 2
            return jnp.sin(x) * 2
        return jax.jit(_packed)

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    x = jnp.ones(8)
    try:
        for k, v in zip(keys, (str(tmp_path), 0, 0)):
            jax.config.update(k, v)
        cc.reset_cache()
        step(False)(x)
        scoped = step(True)
        scoped(x)                            # runs the unscoped program
        assert "mlp" not in scoped.lower(x).compile().as_text()
        _, table = traced.compiled_scopes(scoped, (x,))
        assert "mlp" in table.values()
    finally:
        for k, v in before.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_work_counters_match_the_step_inputs_on_rag_4k():
    """ctx_positions is the sum of each valid lane's kv_len the packed step
    is handed, lanes_valid their count, sampled_lanes the tokens returned:
    the model FLOPs from the counters equal the harness's count from the
    step inputs. The rag-4k mix's first requests at tiny width."""
    from bench import harness, traced
    c = _tiny_config()                         # rag-4k's serving shape
    eng = _engine(c)
    seen, _ = _recording(eng)
    items = G.generate(registry.mix("rag-4k"), 256, SEED, 1.0).items[:3]
    from repro.serve import Request
    for it in items:
        eng.submit(Request(uid=it.uid, prompt=it.prompt, max_new_tokens=2))
    before = eng.padding_stats()
    done = eng.run()
    after = eng.padding_stats()
    d = {k: after[k] - before[k] for k in ("lanes_valid", "ctx_positions",
                                           "sampled_lanes")}
    contexts = harness.step_contexts([a[5] for a in seen])
    assert d["ctx_positions"] == sum(int(kv.sum()) for kv in contexts)
    assert d["lanes_valid"] == sum(len(kv) for kv in contexts)
    tokens = sum(len(r.out_tokens) for r in done)
    assert d["sampled_lanes"] == tokens == 6
    arch = registry.arch(c["arch"])
    wrapper = (sum(arch.step_flops(c, kv, 0) for kv in contexts)
               + tokens * arch.head_flops(c))
    counted = (d["lanes_valid"] * arch.matmul_flops_per_token(c)
               + arch.attention_flops(c, d["ctx_positions"])
               + d["sampled_lanes"] * arch.head_flops(c))
    assert counted == wrapper == traced.counter_flops(arch, c, d)


def test_traced_windows_on_the_cpu(tmp_path, monkeypatch):
    """bench/traced.py end to end at a tiny size on the CPU: the window is
    traced and read, the engine's spans found, both FLOP counts agree, and
    every step is reported as long with the threshold at zero."""
    from bench import traced
    root = tmp_path / "bench"
    for d in ("configs", "traffic", "checks"):
        (root / d).mkdir(parents=True)
    shutil.copy(registry.ROOT / "peaks.json", root)
    shutil.copytree(registry.ROOT / "archs", root / "archs",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "checks" / "tiny.closed.json").write_text(json.dumps(
        {"requests": 8, "logit_gap_max": {"limit": 0.05}}))
    c = _tiny_config(max_batch=4, max_len=128, block_size=8, num_blocks=80)
    (root / "configs" / "tiny.json").write_text(json.dumps(c))
    (root / "traffic" / "closed.json").write_text(json.dumps(dict(
        loop="closed", clients=4, per_client=400, lead_in_s=0.5,
        prompt={"dist": "uniform", "min": 8, "max": 40},
        output={"dist": "uniform", "min": 4, "max": 12})))
    bench = registry.load_benchmark()
    bench["workloads"] = [{"name": "tiny.closed", "config": "tiny",
                           "traffic": "closed", "chips": 1, "why": "test"}]
    monkeypatch.setattr(traced, "LONG_STEP_S", 0.0)
    w = traced.run("tiny.closed", SEED, 2.0, window_s=0.5, root=root,
                   bench=bench, require_tpu=False)
    assert w["steps"] > 0 and w["programs"] >= 1
    assert w["flops_wrapper"] == w["flops_counters"] > 0
    assert w["long_steps"] and all(
        "serve.sample" in s["host_s"] for s in w["long_steps"])
