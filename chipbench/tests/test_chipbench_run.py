"""The whole harness on the CPU at a tiny size: a run, its control, the
faults it must catch, its refusals, a cell added from data alone and an
architecture added from new files alone."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from chipbench import run
from chipbench.tests import tiny

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**33 + 17


def _run(kind="offline", log=None, **kw):
    return run.run_cell(tiny.spec(kind), seed=SEED, seconds=1.0,
                        trace=False, require_chip=False,
                        log=log or (lambda *a: None), **kw)


@pytest.mark.parametrize("kind", ["offline", "closed", "open"])
def test_served_tokens_match_the_reference(kind):
    """Wave prefill, batch-1 refill and paged decode, as the window served
    them, against the float32 reference."""
    lines = []
    res = _run(kind, log=lines.append)
    assert res["correct"] is True and res["failed"] == 0
    c = res["checks"]
    for name in ("logit_gap_max", "logit_err_mean"):
        assert c[name]["value"] <= c[name]["limit"]
    assert any(x.startswith("compiles_in_window: events 0 backend 0")
               for x in lines)
    assert res["attempted"] > 0 and c["sampled_tokens"]["value"] > 0
    assert c["sampled_decode_tokens"]["value"] > 0
    m = res["metrics"]
    assert m["tokens_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("control", ["int8", "fp8"])
def test_control_comes_out_not_correct(control):
    """The control, put in the program's place, goes through the same
    comparison and fails it."""
    res = _run(controls=(control,))
    assert res["correct"] is False and res["failed"] > 0
    c = res["checks"]
    assert c["logit_err_mean"]["value"] > c["logit_err_mean"]["limit"]


def test_a_token_altered_where_produced_is_caught():
    def tamper(served):
        eng, decode = served.engine, served.engine._run_decode

        def altered():
            toks = np.array(decode())
            if eng.n_decode_steps % 3 == 0:
                toks = (toks + 1) % served.cfg.vocab
            return toks

        eng._run_decode = altered

    res = _run(tamper=tamper)
    assert res["correct"] is False and res["failed"] > 0


def _broken_decode(fault):
    """A tamper that swaps the engine's decode step for one with
    ``fault``: ``state_unchanged`` returns the pool as it came in (the new
    position's K and V are never written); ``half_batch`` serves the odd
    slots as if their context were empty."""
    import jax
    import jax.numpy as jnp

    from repro.models import api as mapi

    def tamper(served):
        api = mapi.build(served.cfg)
        step = jax.jit(api.decode_paged)

        def broken(p, token, pcache):
            if fault == "half_batch":
                odd = jnp.arange(pcache["lens"].shape[0]) % 2 == 1
                pcache = {**pcache,
                          "lens": jnp.where(odd, 0, pcache["lens"])}
            logits, out = step(p, token, pcache)
            return logits, (pcache if fault == "state_unchanged" else out)

        served.engine._decode_fn = broken

    return tamper


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_decode_step_is_caught(fault):
    res = _run(tamper=_broken_decode(fault))
    assert res["correct"] is False and res["failed"] > 0


def _env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


def test_refuses_a_cpu_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "q4b.decode.offline", "--seed", "1", "--seconds", "1"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr


def _bench_copy(tmp_path):
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    return tmp_path


def test_without_the_program_exits_nonzero(tmp_path):
    root = _bench_copy(tmp_path)
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "q4b.decode.offline", "--seed", "1", "--seconds", "1"],
        cwd=root, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_new_cell_needs_data_files_and_one_entry(tmp_path):
    """A configuration file, a traffic file and a ``workloads`` entry are
    all a new cell needs: the harness finds them by name and runs them."""
    root = _bench_copy(tmp_path)
    cfg = {k: v for k, v in tiny.CONFIG.items() if k != "_name"}
    tr = {k: v for k, v in tiny.TRAFFIC.items() if k != "_name"}
    (root / "chipbench/configs/tiny-new.json").write_text(json.dumps(cfg))
    (root / "chipbench/traffic/tiny_new.json").write_text(json.dumps(tr))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "tiny.new", "config": "tiny-new",
                               "traffic": "tiny_new", "chips": 1,
                               "why": "a cell added from data alone"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    p = subprocess.run(
        [sys.executable, "-c",
         "import json, sys; sys.path.insert(0, '.'); "
         "from chipbench.run import load_spec; "
         "print(json.dumps(load_spec('tiny.new')))"],
        cwd=root, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    spec = json.loads(p.stdout)
    assert spec["config"]["hidden_size"] == 64
    assert [m["name"] for m in spec["end_to_end"]] == \
        ["tokens_per_s", "setup_s"]
    res = run.run_cell(spec, seed=5, seconds=0.5, trace=False,
                       require_chip=False, log=lambda *a: None)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"tokens_per_s", "setup_s"}


def _add_cell(root, name: str, **config):
    """A configuration and a traffic file named ``name`` (the tiny ones,
    with ``config``'s keys changed) and a ``workloads`` entry for them."""
    cfg = {k: v for k, v in tiny.CONFIG.items() if k != "_name"}
    tr = {k: v for k, v in tiny.TRAFFIC.items() if k != "_name"}
    (root / f"chipbench/configs/{name}.json").write_text(
        json.dumps({**cfg, **config}))
    (root / f"chipbench/traffic/{name}.json").write_text(json.dumps(tr))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": name,
                               "traffic": name, "chips": 1,
                               "why": "a cell added from new files"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_a_new_architecture_needs_only_new_files(tmp_path):
    """A ``model_type`` that no shared module names runs from new files
    alone: its module in ``chipbench/arch/`` (here one that re-exports
    qwen2's), a configuration naming it, a traffic file and a ``workloads``
    entry.  The run goes through that module and comes out correct."""
    root = _bench_copy(tmp_path)
    (root / "chipbench/arch/qwen2_alias.py").write_text(
        '"""Qwen2 under another model_type."""\n'
        "from chipbench.arch.qwen2 import *  # noqa: F401,F403\n")
    _add_cell(root, "tiny_alias", model_type="qwen2_alias")
    script = (
        "import json, sys; sys.path[:0] = ['.', sys.argv[1]]; "
        "from chipbench import arch, run; "
        "spec = run.load_spec('tiny_alias'); "
        "res = run.run_cell(spec, seed=5, seconds=0.5, trace=False, "
        "require_chip=False, log=lambda *a: None); "
        "mod = arch.arch_for(spec['config']); "
        "print(json.dumps({'correct': res['correct'], 'file': mod.__file__, "
        "'checks': res['checks']}))")
    p = subprocess.run([sys.executable, "-c", script, str(ROOT / "src")],
                       cwd=root, env=_env(), capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.splitlines()[-1])
    assert Path(out["file"]) == root / "chipbench/arch/qwen2_alias.py"
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["sampled_decode_tokens"]["value"] > 0


def test_an_unknown_architecture_is_refused_by_name(tmp_path):
    """A configuration whose ``model_type`` has no module is refused when
    the cell is looked up, naming the file that was looked for."""
    root = _bench_copy(tmp_path)
    _add_cell(root, "tiny_unknown", model_type="no_such_arch")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "tiny_unknown",
         "--seed", "1", "--seconds", "1"],
        cwd=root, env=_env(), capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert str(Path("chipbench", "arch", "no_such_arch.py")) in p.stderr
