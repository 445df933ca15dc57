"""The cell ``serve-reason-steady`` at the rehearsal's size on the CPU:
the manifest, the kind end to end (``correct`` true; false under the
int8 control and with the recurrent state lost at install), each new
per-layer reader on a hand-made run, and the two copies of the family's
counting functions held equal."""

import importlib.util
import json
import sys
from pathlib import Path
from unittest import mock

import jax.numpy as jnp
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import check_manifest, flops_nemotron_h, manifest  # noqa: E402
from perf import reference_nemotron_h as reference  # noqa: E402
from perf import run as perf_run  # noqa: E402
from perf import traffic_gen  # noqa: E402

CELL = "serve-reason-steady"
NEW = ("hybrid_serve_mfu_pct", "hybrid_decode_roofline",
       "tokens_per_active_expert")


def _run(capsys, *extra, seed=2 ** 31 + 91, seconds="2"):
    rc = perf_run.main(["--workload", CELL, "--seed", str(seed),
                        "--seconds", seconds, "--cpu-dry-run", *extra])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def _reader(name):
    base = manifest.PERF / "metrics" / name
    spec = importlib.util.spec_from_file_location(
        "m_" + name, base.with_name(name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read, json.loads(base.with_name(name + ".json").read_text())


def _dims():
    return reference.dims_of(manifest.cell(CELL)["config"])


def test_manifest_holds_the_cell_and_its_readers():
    assert check_manifest.check() == []
    cell = manifest.cell(CELL)
    assert cell["traffic"]["kind"] == "serve_hybrid" and cell["chips"] == 1
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) <= names
    assert {"batch_occupancy", "decode_step_ms", "device_idle_pct.serve",
            "step_idle_ms.pick", "gateway_queue_ms_p95"} <= names
    # the dense model's counts are not read here
    assert not {"serve_mfu_pct", "paged_decode_roofline"} & names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "req_latency_p95_ms", "serve_tok_s", "setup_s"}


def test_configuration_is_the_catalog_row_cut_as_stated():
    config = manifest.cell(CELL)["config"]
    d = reference.dims_of(config)
    assert (d["D"], d["Hm"], d["P"], d["G"], d["N"], d["K"]) == (
        4096, 128, 64, 8, 128, 4)
    assert (d["latent"], d["F"], d["Fs"], d["router"], d["top_k"]) == (
        1024, 2688, 5376, 512, 22)
    assert (d["pattern"], d["held"], d["first"], d["V"]) == (
        "EMEMEMEMEM*", 128, 0, 32768)
    assert config["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512,
        "vocab_size": 131072,
        "hybrid_override_pattern": config["published"][
            "hybrid_override_pattern"]}
    assert config["published"]["hybrid_override_pattern"].count("*") == 8
    # 4.648 B parameters: 9.30 GB in bfloat16
    total = (flops_nemotron_h.dense_matmul_params(d)
             + 5 * d["held"] * flops_nemotron_h.expert_params(d)
             + d["V"] * d["D"])
    assert abs(total / 4.648e9 - 1) < 0.002
    mix = manifest.cell(CELL)["traffic"]
    sizes = traffic_gen.quantile_sizes(mix["answer_tokens"], 4000)
    assert abs(sizes.mean() / mix["answer_tokens"]["source_mean"] - 1) < 0.03
    assert (mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"]
            <= config["serving"]["slot_len"])


def test_order_keeps_the_windows_close_quiet():
    """The mix's order lets nobody in during the window's last seconds
    and leaves no more in flight than its ``close`` block states."""
    mix = manifest.cell(CELL)["traffic"]
    seconds = float(manifest.load()["run_seconds"])
    requests = traffic_gen.serve_requests(mix, 1, seconds, 32768)
    cut = traffic_gen.in_flight_at_close(requests, seconds, mix["close"])
    assert 0 < len(cut) <= mix["close"]["in_flight_max"]
    assert max(r["due_s"] for r in requests) < seconds - mix["close"][
        "quiet_s"]


def test_cell_rehearsal_comes_out_correct(capsys):
    line, err = _run(capsys, "--trace", "1")
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}
    assert line["compared"]["malformed_timelines"]["value"] == 0
    info = line["info"]
    assert info["tokens_compared"] > 0 and info["recurrent_state_bytes"] > 0
    # the device's counters are read under the gateway's lock, a few
    # steps after the host's at the window's close
    c = info["device_counters"]
    ahead = c["decode_moe_steps_total"] - info["decode_steps"]
    assert 0 <= ahead <= 64
    assert (c["moe_steps_total"] - c["decode_moe_steps_total"]
            == info["prefills"])
    assert 0 < c["experts_active_total"] <= c["expert_assignments_held_total"]
    # one blocking transfer a step, whatever stats() is asked
    assert info["host_syncs"] <= info["decode_steps"] + line["attempted"]
    # counters read on the CPU; a share of a peak or of a trace does not
    assert "tokens_per_active_expert" in line["metrics"]
    assert not {"hybrid_serve_mfu_pct",
                "hybrid_decode_roofline"} & set(line["metrics"])
    assert all(m["value"] is None for m in line["metrics"].values())


def test_control_comes_out_not_correct(capsys):
    line, err = _run(capsys, "--control", "int8,routed_dropped", seed=5,
                     seconds="4")
    assert "FAILED" in err
    assert line["correct"] is False
    gap = line["compared"]["served_token_gap_mean"]
    assert gap["value"] > gap["limit"]
    assert line["info"]["program_served_token_gap_mean"] <= gap["limit"]
    # the widest gap is a reading, not a limit, in this kind
    assert "served_token_gap" not in line["compared"]
    assert (line["info"]["served_token_gap"]
            > line["info"]["program_served_token_gap"])
    # the first control named goes through the verdict, each one's
    # readings to info
    controls = line["info"]["controls"]
    assert list(controls) == ["int8", "routed_dropped"]
    assert controls["int8"]["not_correct_by"] == ["served_token_gap_mean"]
    assert controls["int8"]["served_token_gap_mean"] == gap["value"]


def test_planted_routed_faults_are_controls_of_the_reference():
    """``routed_dropped`` and ``experts_shifted`` change the expert
    layers' routed part and nothing else; the second is the share a
    wrong ``experts_held_first`` would compute."""
    import numpy as np
    d = reference.dims_of(manifest.cell(CELL, dry_run=True)["config"])
    weights = reference.init_weights(d, 3, jnp.float32)
    rows = np.arange(24, dtype=np.int32).reshape(2, 12) % d["V"]
    plain = reference.forward_logits(weights, rows, d)
    dropped = reference.forward_logits(weights, rows, d, "routed_dropped")
    shifted = reference.forward_logits(weights, rows, d, "experts_shifted")
    moved = dict(d, first=d["first"] + d["held"])
    assert jnp.array_equal(
        shifted, reference.forward_logits(weights, rows, moved))
    assert not jnp.array_equal(plain, dropped)
    assert not jnp.array_equal(plain, shifted)
    with pytest.raises(ValueError):
        reference.forward_logits(weights, rows, d, "int4")


def test_one_wrong_token_comes_out_not_correct(capsys):
    """The fault the mean gap lets by at the cell's size (one token of
    5,000): the count of tokens far below the reference's best is held
    to 0, so a single one fails the run."""
    from perf.kinds import serve_hybrid
    real = serve_hybrid._check

    def one_wrong(done, *rest):
        longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
        longest["tokens"][-1] = (longest["tokens"][-1] + 7) % 256
        return real(done, *rest)

    with mock.patch.object(serve_hybrid, "_check", one_wrong):
        line, err = _run(capsys, seed=8, seconds="3")
    assert line["correct"] is False
    assert line["compared"]["served_tokens_far"] == {"value": 1, "limit": 0}


def test_state_lost_at_install_comes_out_not_correct(capsys):
    """The planted fault this family can have and no other: the slot
    keeps the state it had (here none) instead of the prefill's. Every
    token after the first is then picked from a wrong state."""
    from kubeflow_rm_tpu.models import paging
    real = paging.paged_install

    def forgetful(cache, *args):
        *strip, (ssm, conv, counts) = args
        return real(cache, *strip,
                    (jnp.zeros_like(ssm), jnp.zeros_like(conv), counts))

    with mock.patch.object(paging, "paged_install", forgetful):
        line, err = _run(capsys, seed=6, seconds="3")
    assert line["correct"] is False
    assert "compared served_token_gap" in err and "FAILED" in err


# -- the new readers, on hand-made runs ---------------------------------


def _hand_run(active_a_step, steps=1000, live=20.0, device_ms=11.0):
    """A window of ``steps`` decode steps with ``live`` slots each at
    some 400 positions; the trace holds twelve runs of the decode
    program of ``device_ms``."""
    runs = [("jit_paged_decode_step(123)", int(i * 14e6), int(device_ms * 1e6))
            for i in range(12)]
    spans = [{"prompt_len": 100, "lo": 1, "hi": 1 + steps, "prefill": False}
             for _ in range(int(live))]
    return {
        "dims": _dims(), "window_s": 14.0, "notes": {},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": {"modules": runs, "ops": [], "host": []},
        "stamped": {"spans": spans},
        "counters": {"decode_steps": steps, "occupancy_sum": live * steps,
                     "slots": 32, "decode_moe_steps_total": steps,
                     "decode_experts_active_total": active_a_step * steps,
                     "expert_assignments_held_total": 5 * 110.0 * steps,
                     "experts_active_total": active_a_step * steps}}


def test_roofline_counts_the_experts_that_met_a_token_not_all_held():
    read, params = _reader("hybrid_decode_roofline")
    d = _dims()
    # 20 live slots, 5.5 assignments each over 128 experts a layer:
    # some 74 of 128 are active, 370 over the five layers
    run = _hand_run(active_a_step=370.0)
    share = read(run, params)
    note = run["notes"]["hybrid_decode_roofline"]
    assert note["runs"] == 10 and abs(note["device_ms_a_run"] - 11.0) < 1e-9
    want = flops_nemotron_h.decode_step_bytes(d, 370.0, 20.0, 20 * 600.5)
    assert abs(share - 100 * want / 819e9 / 11e-3) < 1e-9
    assert 60 < share < 100
    # with every held expert counted (5 x 128) the same step would read
    # over 100 %: the fault the counter is there to prevent
    assert read(_hand_run(active_a_step=640.0, device_ms=11.0),
                params) > 100
    # nothing to read: no trace, no counter (the parent), no run
    for broken in ({"trace": None}, {"peaks": None}):
        assert read({**_hand_run(370.0), **broken}, params) is None
    silent = _hand_run(370.0)
    del silent["counters"]["decode_moe_steps_total"]
    assert read(silent, params) is None


def test_mfu_counts_assignments_held_and_the_state():
    read, params = _reader("hybrid_serve_mfu_pct")
    d = _dims()
    run = _hand_run(370.0)
    share = read(run, params)
    tokens = 20 * 1000
    positions = 20 * (1000 * 100 + (1 + 1000) * 1000 / 2.0)
    want = flops_nemotron_h.serve_flops(d, tokens, 5 * 110.0 * 1000,
                                        positions)
    assert abs(share - 100 * want / (14.0 * 197e12)) < 1e-9
    assert 0 < share < 100
    # a prompt whose first token was stamped inside is prefilled whole
    run2 = _hand_run(370.0)
    run2["stamped"]["spans"][0].update(lo=0, prefill=True)
    assert read(run2, params) > share
    silent = _hand_run(370.0)
    del silent["counters"]["expert_assignments_held_total"]
    assert read(silent, params) is None


def test_tokens_per_active_expert_reads_the_two_counters():
    read, params = _reader("tokens_per_active_expert")
    assert abs(read(_hand_run(370.0), params) - 550.0 / 370.0) < 1e-12
    assert read({"counters": {}}, params) is None


# -- the program's copy of the counts -----------------------------------


@pytest.mark.parametrize("dry", [False, True], ids=["published", "rehearsal"])
def test_program_and_benchmark_count_alike(dry):
    from kubeflow_rm_tpu.utils import flops as program
    from perf.kinds import serve_hybrid
    config = manifest.cell(CELL, dry_run=dry)["config"]
    d, cfg = reference.dims_of(config), serve_hybrid.nemotron_config(config)
    assert (program.hybrid_dense_matmul_params(cfg)
            == flops_nemotron_h.dense_matmul_params(d))
    assert (program.hybrid_expert_params(cfg)
            == flops_nemotron_h.expert_params(d))
    assert (program.hybrid_state_flops_per_token(cfg)
            == flops_nemotron_h.state_flops_per_token(d))
    assert (program.hybrid_serve_flops(cfg, 1e4, 3e4, 5e6)
            == flops_nemotron_h.serve_flops(d, 1e4, 3e4, 5e6))
    assert (program.hybrid_decode_step_bytes(cfg, 300.0, 18.0, 9e3)
            == flops_nemotron_h.decode_step_bytes(d, 300.0, 18.0, 9e3))
