"""chip_smoke.py refuses a CPU before compiling anything; the smoke's
phase functions rehearse on the CPU at tiny sizes (``-m slow``)."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _no_compile(monkeypatch, module):
    """Everything the entry point compiles comes after its
    enable_compile_cache() call — reaching it on a CPU fails the test."""
    def reached():
        raise AssertionError("went past the platform check on a CPU")
    monkeypatch.setattr(module, "enable_compile_cache", reached)


def test_chip_smoke_refuses_cpu_before_any_compile(monkeypatch, capsys):
    _no_compile(monkeypatch, chip_smoke)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main()
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)       # names what it found
    assert '"ok"' not in capsys.readouterr().out   # and prints no result


@pytest.mark.slow
def test_phase_functions_rehearse_on_cpu(tmp_path):
    """The same functions main() runs at the flagship width, at a size a
    CPU compiles in a minute, Pallas in interpret mode."""
    from gsc_tpu.runtime import device_summary

    device = device_summary()
    assert device["platform"] == "cpu"
    kernel = chip_smoke.phase_kernel(device, graphs=5, nodes=8, features=4,
                                     interpret=True, steady_calls=1)
    assert set(kernel) == {"float32", "bfloat16"}
    argv = chip_smoke.phase_configs(
        str(tmp_path),
        agent_overrides={
            "episode_steps": 4, "GNN_features": 4, "GNN_num_layers": 1,
            "GNN_num_iter": 1, "actor_hidden_layer_nodes": [8],
            "critic_hidden_layer_nodes": [8], "mem_limit": 64,
            "batch_size": 4, "nb_steps_warmup_critic": 4},
        sim_overrides={"max_flows": 32})
    results = str(tmp_path / "results")
    train = chip_smoke.phase_train(argv, results, device, replicas=2,
                                   episodes=3, chunk=2)
    assert len(train["returns"]) == 3
    serve = chip_smoke.phase_serve(argv, train["checkpoint"], results,
                                   device, requests=8, concurrency=2)
    assert sum(serve["flushed_buckets"].values()) == 8


# ------------------------------------------------- the one compile-cache rule
def test_compile_cache_rule(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins when set; otherwise the cache is the
    fixed <checkout>/.jax_cache — and no other code in the tree sets the
    directory."""
    import re

    from gsc_tpu import runtime

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert runtime.compile_cache_dir() == "/some/dir"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert runtime.compile_cache_dir() == os.path.join(REPO, ".jax_cache")
    # conftest applied the rule to this process through the same helper
    import jax
    assert jax.config.jax_compilation_cache_dir == runtime.compile_cache_dir()

    setter = re.compile(r"""config\.update\(\s*["']jax_compilation_cache_dir""")
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("chiprun_out", "chip_scratch", "results",
                                 "__pycache__")]
        for name in files:
            if name.endswith(".py"):
                path = os.path.join(root, name)
                with open(path) as f:
                    if setter.search(f.read()):
                        offenders.append(os.path.relpath(path, REPO))
    assert offenders == [os.path.join("gsc_tpu", "runtime.py")], offenders


# ------------------------------------------- mesh builders never re-platform
def test_mesh_builders_raise_when_backend_is_short():
    import jax

    from gsc_tpu.parallel import make_mesh, make_train_mesh

    have = len(jax.devices())
    with pytest.raises(ValueError, match=f"has {have}"):
        make_mesh(have + 1)
    with pytest.raises(ValueError, match=f"has {have}"):
        make_train_mesh(have + 1, 1)
    assert jax.devices()[0].platform == "cpu" and len(jax.devices()) == have
    assert make_mesh(have).devices.size == have


# ------------------------------------------ a failed request fails cli serve
def test_cli_serve_exits_nonzero_when_a_request_errors(tmp_path, monkeypatch):
    """Stub backend: the second request is refused.  The JSON still names
    the error, counts only completed requests into rps — and the command
    exits non-zero."""
    import json

    from click.testing import CliRunner

    import gsc_tpu.serve as serve_mod
    from gsc_tpu.cli import cli
    from tests.test_agent import write_tiny_configs

    real_submit = serve_mod.PolicyServer.submit
    calls = []

    def flaky_submit(self, obs):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("stub backend: request refused")
        return real_submit(self, obs)

    monkeypatch.setattr(serve_mod.PolicyServer, "submit", flaky_submit)
    args = [a for a in write_tiny_configs(tmp_path) if a != "--quiet"]
    r = CliRunner().invoke(cli, [
        "serve", *args, "--requests", "4", "--concurrency", "1",
        "--pool-steps", "0", "--no-obs",
        "--result-dir", str(tmp_path / "res")])
    assert r.exit_code != 0
    out = json.loads(next(line for line in r.output.splitlines()
                          if line.startswith("{")))
    assert out["errors"] == 1 and out["completed"] == 3
    assert "request refused" in out["error_detail"][0]
    # 3 completed, not 4 requested (wall_s is rounded to the millisecond)
    assert 2.7 < out["rps"] * out["wall_s"] < 3.3
