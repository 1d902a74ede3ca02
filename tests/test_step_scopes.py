"""What the program writes for a reader of a device trace (PR 23): the
named scopes of the step program, the scope map ``fit`` leaves beside
its span export, and the timers of the input path's work."""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu import telemetry
from distributed_tensorflow_models_tpu.core import train_loop
from distributed_tensorflow_models_tpu.data import datasets, pipeline
from distributed_tensorflow_models_tpu.harness import config as configlib
from distributed_tensorflow_models_tpu.harness import train as trainlib
from distributed_tensorflow_models_tpu.telemetry import scopes as scopelib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY_LM = dict(
    global_batch_size=8,
    num_steps=16,
    vocab_size=97,
    model_kwargs=dict(
        vocab_size=97, num_layers=2, num_heads=2, d_model=32, d_ff=64,
        max_len=16,
    ),
)
FITS = {
    "lm-fused": ("transformer_lm", dict(TINY_LM, fused_unembed=True)),
    "lm-plain": ("transformer_lm", dict(TINY_LM, fused_unembed=False)),
    "lenet": ("lenet_mnist", dict(global_batch_size=32)),
}


def _fit(name, workdir, **overrides):
    config, kwargs = FITS[name]
    cfg = configlib.get_config(
        config,
        train_steps=4,
        log_every_steps=2,
        checkpoint_every_steps=None,
        checkpoint_every_secs=1e9,
        **{**kwargs, **overrides},
    )
    trainlib.fit(cfg, str(workdir))


def _forward(op_name):
    return "jvp(" in op_name and "transpose(" not in op_name


def _has(op_name, scope):
    # A whole path element, bare or inside a transform's brackets.
    return any(
        f"{a}{scope}{b}" in f"/{op_name}/" for a in "/(" for b in "/)"
    )


@pytest.mark.parametrize("name", sorted(FITS))
def test_fit_leaves_a_scope_map_and_input_work_rows(name, tmp_path):
    _fit(name, tmp_path, trace_export=True)
    found = json.load(open(tmp_path / "step_scopes_p0.json"))
    assert found["version"] == scopelib.SCOPES_VERSION
    fused = found["fused"]["jit_one_step"]
    for fusion, held in fused["inside"].items():
        assert fusion in found["modules"]["jit_one_step"]
        assert held == sorted(set(held)) and held[-1] < len(fused["names"])
    # One program ran: the single-step jit (or its AOT executable).
    assert list(found["modules"]) == ["jit_one_step"]
    op_names = set(found["modules"]["jit_one_step"].values())
    assert any(_has(n, "optimizer") for n in op_names)
    assert any(_forward(n) for n in op_names)
    assert any("transpose(" in n for n in op_names)
    # Nothing of the optimizer is under a transform, and the other way.
    assert not any(_has(n, "optimizer") and "jvp(" in n for n in op_names)
    if name.startswith("lm"):
        for scope in ("attention_core", "unembed_loss"):
            under = [n for n in op_names if _has(n, scope)]
            assert any(_forward(n) for n in under), scope
            if (name, scope) == ("lm-fused", "unembed_loss"):
                # The fused head makes its gradient products while each
                # chunk's logits are live (ops/losses.py::
                # fused_unembed_mean_xent), so they count as forward;
                # its backward is a scaling by the loss's cotangent,
                # the constant 1 here, which XLA folds away.
                assert all(_forward(n) for n in under)
                assert any(n.endswith("/dot_general") for n in under)
            else:
                assert any("transpose(" in n for n in under), scope
    else:
        assert not any(_has(n, "attention_core") for n in op_names)

    rows = [json.loads(ln) for ln in open(tmp_path / "metrics.jsonl")]
    telem = [r for r in rows if "data_wait_s" in r]
    assert telem and all(
        r["assemble_s"] > 0 and r["shard_s"] > 0 for r in telem
    )
    spec = importlib.util.spec_from_file_location(
        "check_metrics_schema",
        os.path.join(REPO, "scripts", "check_metrics_schema.py"),
    )
    schema = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(schema)
    errors, _, n_telem = schema.check_lines(open(tmp_path / "metrics.jsonl"))
    assert not errors and n_telem == len(telem)
    partial = dict(telem[0])
    del partial["shard_s"]
    errors, _, _ = schema.check_lines([json.dumps(partial)])
    assert any("input-work" in e for e in errors)

    snap = json.load(open(tmp_path / "telemetry.json"))["metrics"]
    # One hook walk, one placed batch and one produced batch per step at
    # least (the prefetch stages run ahead of the loop).
    assert snap[f"{telemetry.HOOKS}/count"] == 4
    assert snap[f"{telemetry.SHARD}/count"] >= 4
    assert (
        snap[f"{telemetry.ASSEMBLE}/count"] >= snap[f"{telemetry.SHARD}/count"]
    )
    assert snap[telemetry.PIPELINE_BYTES] > 0
    assert 0 < snap[telemetry.STARTUP_AOT_LOWER] <= snap[
        telemetry.STARTUP_AOT_COMPILE
    ]
    events = json.load(open(tmp_path / "trace_p0.json"))["traceEvents"]
    names = {e["name"] for e in events}
    assert {"train/hooks", "startup/aot_lower", "fit/step_scopes"} <= names
    cost = next(e for e in events if e["name"] == "fit/step_scopes")["args"]
    assert cost["instructions"] == len(found["modules"]["jit_one_step"])
    assert cost["file_bytes"] == os.path.getsize(
        tmp_path / "step_scopes_p0.json"
    )


def test_no_scope_map_without_trace_export(tmp_path):
    _fit("lenet", tmp_path, trace_export=False)
    assert os.path.isfile(tmp_path / "telemetry.json")
    assert not os.path.exists(tmp_path / "step_scopes_p0.json")
    assert not os.path.exists(tmp_path / "trace_p0.json")


HLO = textwrap.dedent(
    """\
    HloModule jit_step, is_scheduled=true, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

    %fused_computation (param_0: f32[8]) -> f32[8] {
      %param_0 = f32[8]{0} parameter(0)
      ROOT %inner.1 = f32[8]{0} sine(f32[8]{0} %param_0), metadata={op_name="jit(step)/jvp(Net)/inside_a_fusion"}
    }

    %region_0.3 (a: f32[], b: f32[]) -> f32[] {
      %a = f32[] parameter(0)
      %b = f32[] parameter(1)
      ROOT %add.9 = f32[] add(f32[] %a, f32[] %b), metadata={op_name="jit(step)/optimizer/reducer"}
    }

    %body.5 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
      %p = (s32[], f32[8]{0}) parameter(0)
      %gte.1 = f32[8]{0} get-tuple-element((s32[], f32[8]{0}) %p), index=1
      %fusion.2 = f32[8]{0} fusion(f32[8]{0} %gte.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/while/body/transpose(jvp(Net))/Dense_0/mul" source_file="a \\"quoted\\" path.py" source_line=3}
      ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.0, %fusion.2)
    }

    ENTRY %main.7 (Arg_0.1: f32[8]) -> f32[8] {
      %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="state.params['w']"}
      %copy.4 = f32[8]{0} copy(f32[8]{0} %Arg_0.1)
      %while.6 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond.4, body=%body.5, metadata={op_name="jit(step)/while"}
      %reduce.8 = f32[] reduce(f32[8]{0} %copy.4, f32[] %c), dimensions={0}, to_apply=%region_0.3, metadata={op_name="jit(step)/optimizer/reduce_sum"}
      ROOT %gte.9 = f32[8]{0} get-tuple-element(%while.6), index=1
    }
    """
)


def test_parse_hlo_keeps_what_a_trace_can_name():
    module, scopes, fused = scopelib.parse_hlo(HLO)
    assert module == "jit_step"
    # The fusion grew from a backward instruction and holds a forward
    # one: its inside is listed, by index into the names.
    assert fused["inside"] == {"fusion.2": [0]}
    assert fused["names"] == ["jit(step)/jvp(Net)/inside_a_fusion"]
    assert scopes == {
        # The fusion instruction itself, in a while body, escapes kept.
        "fusion.2": "jit(step)/while/body/transpose(jvp(Net))/Dense_0/mul",
        "while.6": "jit(step)/while",
        "reduce.8": "jit(step)/optimizer/reduce_sum",
    }  # not: a fusion's inside, a reducer, a parameter, bare copies


class _Program:
    def __init__(self, text):
        self._text = text

    def as_text(self):
        if isinstance(self._text, Exception):
            raise self._text
        return self._text


@pytest.mark.parametrize(
    "text", [None, "", RuntimeError("no HLO in this executable")]
)
def test_a_program_without_text_writes_no_map_and_raises_nothing(
    text, tmp_path, caplog
):
    path = str(tmp_path / "step_scopes_p0.json")
    assert scopelib.write_step_scopes(path, lambda: [_Program(text)]) is None
    assert not os.path.exists(path)
    assert "step scopes" in caplog.text
    cost = scopelib.write_step_scopes(
        path, lambda: [_Program(text), _Program(HLO)]
    ) if not isinstance(text, Exception) else None
    if cost is not None:
        assert cost["modules"] == 1 and cost["instructions"] == 3


def test_the_jit_path_gives_its_program_too():
    """Without an AOT handle the executable is the jit cache's own: the
    wrapper lowers the call's abstract arguments again, after donation
    has deleted the buffers."""

    def step(state, batch, rng):
        with jax.named_scope(train_loop.OPTIMIZER_SCOPE):
            return state + jnp.sum(batch["x"]), {}

    istep = train_loop.InstrumentedStep(
        jax.jit(step, donate_argnums=(0,)),
        registry=telemetry.MetricsRegistry(),
    )
    assert istep.executables() == []
    state = jnp.zeros(())
    for n in (4, 4, 6):  # two batch signatures
        state, _ = istep(state, {"x": jnp.ones((n,))}, None)
    programs = istep.executables()
    assert len(programs) == 2
    for program in programs:
        module, scopes, _ = scopelib.parse_hlo(program.as_text())
        assert module == "jit_step"
        assert any("optimizer" in v for v in scopes.values())


def test_a_cached_executable_cannot_serve_a_renamed_scope(tmp_path):
    """jax leaves metadata out of the persistent cache's key by default,
    so a program cached before a scope was renamed would come back with
    the old names in its text.  ``apply_compile_cache`` puts the
    metadata into the key: the renamed program is another entry.  The
    key must not depend on who lowers the program (the three calls below
    are three call sites): locations hold one frame, not the callers."""
    script = textwrap.dedent(
        """
        import os, sys
        import jax, jax.numpy as jnp
        from distributed_tensorflow_models_tpu.harness import startup
        cache = startup.apply_compile_cache(sys.argv[1])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        def program(scope):
            def f(x):
                with jax.named_scope(scope):
                    return jnp.sin(x) * 2 + 1
            return jax.jit(f).lower(jnp.ones((8,))).compile()
        def entries():
            return sum(len(fs) for _, _, fs in os.walk(cache))
        program("scope_before"); n1 = entries()
        jax.clear_caches()
        program("scope_before"); n2 = entries()
        jax.clear_caches()
        text = program("scope_after").as_text(); n3 = entries()
        print(n1, n2, n3, "scope_after" in text, "scope_before" in text)
        """
    )
    out = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path / "cache")],
        capture_output=True, text=True, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": ""},
    )
    assert out.returncode == 0, out.stderr
    n1, n2, n3, new, old = out.stdout.split()[-5:]
    assert int(n1) > 0  # the cache is on and took the small program
    assert n2 == n1  # the same program from another line: a cache read
    assert int(n3) > int(n1)  # the renamed scope: compiled, a new entry
    assert (new, old) == ("True", "False")


class _FiniteDataset:
    """``n`` batches through the worker-pool split, then the end."""

    def __init__(self, n):
        self._n, self._i = n, 0

    def next_work(self):
        if self._i >= self._n:
            raise StopIteration
        self._i += 1
        return self._i - 1

    def assemble(self, i):
        return {
            "image": np.full((8, 4), i, np.float32),
            "label": np.full((8,), i, np.int32),
        }

    def __iter__(self):
        return datasets.iterate_via_work(self)


@pytest.mark.parametrize("num_workers", [1, 3])
def test_input_work_is_counted_once_per_batch(num_workers, mesh8):
    reg = telemetry.MetricsRegistry()
    reg.trace = telemetry.Tracer(capacity=256)
    n = 11
    host = pipeline.HostPipeline(
        _FiniteDataset(n), prefetch=2, num_workers=num_workers, registry=reg
    )
    placed = list(
        pipeline.DevicePrefetcher(host, mesh8, depth=2, registry=reg)
    )
    host.stop()
    assert [int(b["label"][0]) for b in placed] == list(range(n))
    snap = reg.snapshot()
    assert snap[f"{telemetry.ASSEMBLE}/count"] == n
    assert snap[f"{telemetry.SHARD}/count"] == n
    assert snap[telemetry.PIPELINE_BYTES] == n * (8 * 4 * 4 + 8 * 4)
    assert snap[f"{telemetry.ASSEMBLE}/total_s"] > 0
    assert snap[f"{telemetry.SHARD}/total_s"] > 0
    # In the ring like the pipeline's waits: from a millisecond up, and
    # a placement carries its bytes.
    for e in reg.trace.events():
        if e["name"] == telemetry.SHARD:
            assert e["args"] == {"bytes": 8 * 4 * 4 + 8 * 4}
            assert e["dur_s"] >= 1e-3
