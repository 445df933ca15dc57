"""The chip smoke's CPU rehearsal, and the bring-up rules it rests on:
kernel choice keyed on the computation's devices, a compile cache that
can be placed from outside, and a benchmark that fails loudly."""

import json
import sys
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import bench  # noqa: E402
import chip_smoke  # noqa: E402
from kubeflow_rm_tpu.ops import attention  # noqa: E402
from kubeflow_rm_tpu.utils import compile_cache  # noqa: E402


@pytest.fixture(autouse=True)
def _cache_placed_outside(monkeypatch, tmp_path):
    """Entry points under test call ``enable_compile_cache``; with the
    variable set it touches nothing, so no test writes into the
    checkout."""
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cache"))


# -- chip_smoke.py ----------------------------------------------------------


def test_cpu_dry_run_drives_both_phases(capsys):
    assert chip_smoke.main(["--cpu-dry-run"]) == 0
    summary, last = capsys.readouterr().out.strip().splitlines()[-2:]
    # the last line is the driver's contract: these keys and no others
    last = json.loads(last)
    assert set(last) == {"ok", "device"} and last["ok"] is True
    assert set(last["device"]) == {"platform", "kind", "count"}
    assert last["device"]["platform"] == "cpu"
    assert isinstance(last["device"]["count"], int)
    assert summary.startswith("summary: ")
    result = json.loads(summary.removeprefix("summary: "))
    assert result["device"] == "cpu" and result["claim"] is None
    serve, train = result["serve"], result["train"]
    assert serve["tokens_returned"] == 60 and serve["requests"] == 9
    assert len(serve["prefill_buckets"]) >= 3
    assert serve["prefix_hit_tokens"] > 0 and serve["cow_forks"] >= 1
    assert serve["token_identical_to_generate_fused"] == "9/9"
    assert train["loss"][-1] < train["loss"][0]
    # the rehearsal walked the pallas kernels (interpreted), and says so
    assert {"flash"} == {c["kernel"] for c in train["attention"]}
    assert all(c["interpret"] for c in train["attention"])
    decode = [c for c in serve["attention"]
              if c["kernel"].startswith("paged_decode")]
    assert decode and all(c["kernel"] == "paged_decode"
                          and c["interpret"] for c in decode)
    # interpreted, the kernel lowers to plain HLO: no custom call here
    assert serve["pallas_kernels_in_lowered_decode_step"] == 0
    assert serve["kv_blocks_read_total"] > serve["decode_steps"]
    # one pick program and one blocking transfer a token boundary: the
    # decode steps and the boundaries that only retired
    assert (serve["decode_steps"] <= serve["host_syncs_total"]
            == serve["pick_programs_total"]
            <= serve["decode_steps"] + serve["requests"])


def test_a_failure_in_either_phase_exits_nonzero(monkeypatch, capsys):
    from kubeflow_rm_tpu.models.generate import ContinuousBatchingEngine
    from kubeflow_rm_tpu.training import loop

    def boom(*a, **kw):
        raise RuntimeError("injected")

    monkeypatch.setattr(ContinuousBatchingEngine, "step", boom)
    monkeypatch.setattr(loop, "fit", boom)
    assert chip_smoke.main(["--cpu-dry-run"]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out and "summary:" not in out
    assert "FAILED ['serve', 'train']" in err and "injected" in err


def test_without_the_flag_no_tpu_is_a_failure(capsys):
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert '"ok"' not in out and "need a tpu" in err


# -- attention dispatch keys on the computation's devices -------------------


def _qkv(sharding=None):
    x = jnp.ones((8, 128, 2, 16), jnp.float32)
    return jax.device_put(x, sharding) if sharding else x


def test_computation_devices_reads_operands_not_the_process(devices8):
    assert jax.device_count() == 8
    assert attention.computation_devices(_qkv()) == ("cpu", 1)
    mesh = jax.make_mesh((8,), ("x",), devices=devices8,
                         axis_types=(jax.sharding.AxisType.Auto,))
    spread = _qkv(NamedSharding(mesh, P("x")))
    assert attention.computation_devices(spread) == ("cpu", 8)
    seen = []
    jax.jit(lambda a: seen.append(attention.computation_devices(a)) or a
            )(spread)
    jax.jit(lambda a: seen.append(attention.computation_devices(a)) or a
            )(_qkv())
    assert seen == [("cpu", 8), ("cpu", 1)]
    # a mesh the caller passes wins over the operand
    one_chip = types.SimpleNamespace(devices=np.array(
        [types.SimpleNamespace(platform="tpu")], dtype=object))
    assert attention.computation_devices(spread, one_chip) == ("tpu", 1)


def test_auto_picks_flash_for_one_device_programs_on_a_multichip_host(
        monkeypatch, devices8):
    """Eight devices visible, as on a four-chip host: a one-device
    program still gets the kernel; a program laid out over the mesh
    keeps the XLA path."""
    from kubeflow_rm_tpu.ops import flash_attention as fa
    calls = []

    def kernel(q, k, v, **kw):
        calls.append(kw["interpret"])
        return q

    monkeypatch.setattr(fa, "flash_attention", kernel)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert jax.device_count() == 8

    def attend(x):
        return attention.dot_product_attention(x, x, x, causal=True)

    jax.jit(attend)(_qkv())
    assert calls == [False]         # compiled kernel, never interpreted
    mesh = jax.make_mesh((8,), ("x",), devices=devices8,
                         axis_types=(jax.sharding.AxisType.Auto,))
    jax.jit(attend)(_qkv(NamedSharding(mesh, P("x"))))
    assert calls == [False]         # multi-device: XLA path


def test_flash_refuses_the_interpreter_on_a_tpu_computation(monkeypatch):
    from kubeflow_rm_tpu.ops.flash_attention import flash_attention
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="interpret"):
        jax.jit(lambda x: flash_attention(x, x, x, interpret=True)
                )(_qkv())


# -- compile cache ----------------------------------------------------------


def test_compile_cache_leaves_a_placed_directory_alone(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    assert compile_cache.enable_compile_cache() == "/some/dir"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(compile_cache.ENV_VAR)
    try:
        got = compile_cache.enable_compile_cache()
        assert got == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# -- bench.py fails loudly --------------------------------------------------


def test_bench_propagates_a_failing_step(monkeypatch):
    from kubeflow_rm_tpu.training import train

    def broken(*a, **kw):
        def step(state, batch):
            raise RuntimeError("injected step failure")
        return step

    monkeypatch.setattr(train, "make_train_step", broken)
    with pytest.raises(RuntimeError, match="injected step failure"):
        bench.main(["--preset", "tiny"])


def test_bench_without_a_chip_needs_an_explicit_tiny():
    with pytest.raises(SystemExit) as exc:
        bench.main([])
    assert exc.value.code not in (0, None)
