"""The cell ``serve-reason-long`` at the rehearsal's size on the CPU:
the manifest, the configuration against the catalog row, the kind end
to end (``correct`` true; false under the int8 control, each planted
fault of the reference and the state lost at install), each new
per-layer reader on a hand-made run, and the two copies of the family's
counting functions held equal."""

import importlib.util
import json
import sys
from pathlib import Path
from unittest import mock

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import check_manifest, flops_qwen3_next, manifest  # noqa: E402
from perf import reference_qwen3_next as reference  # noqa: E402
from perf import run as perf_run  # noqa: E402
from perf import traffic_gen  # noqa: E402

CELL = "serve-reason-long"
NEW = ("deltanet_decode_roofline", "expert_ffn_roofline",
       "deltanet_serve_mfu_pct")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def _run(capsys, *extra, seed=2 ** 31 + 97, seconds="2"):
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
    assert cell["traffic"]["kind"] == "serve_qwen3_next"
    assert cell["chips"] == 1
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | {"tokens_per_active_expert"} <= names
    assert {"batch_occupancy", "decode_step_ms", "device_idle_pct.serve",
            "gateway_queue_ms_p95"} <= names
    assert {f"step_idle_ms.{s}" for s in (
        "admit", "pick", "dispatch", "scatter", "gateway",
        "unattributed")} <= names
    # the other families' counts are not read here
    assert not {"serve_mfu_pct", "paged_decode_roofline",
                "hybrid_serve_mfu_pct", "hybrid_decode_roofline"} & names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "req_latency_p95_ms", "serve_tok_s", "setup_s"}
    # the three new metrics read this cell alone
    bench = manifest.load()
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]


def test_configuration_is_the_catalog_row_cut_as_stated():
    config = manifest.cell(CELL)["config"]
    reduced = {"num_hidden_layers": 4, "num_experts": 256,
               "vocab_size": 75968}
    if CATALOG.is_file():
        row = next(json.loads(line) for line in CATALOG.open()
                   if json.loads(line)["name"]
                   == "Qwen3-Next-80B-A3B-Instruct")
        entry = next(c for c in manifest.load()["configs"]
                     if c["name"] == "qwen3-next-serve-l4-e256")
        assert entry["source"] == row["source_url"]
        assert sorted(entry["reduced"]) == sorted(reduced)
        for k, v in row["config"].items():
            assert config[k] == (reduced[k] if k in reduced else v), k
        assert config["published"] == {k: row["config"][k] for k in reduced}
    d = reference.dims_of(config)
    assert (d["D"], d["Hk"], d["Hv"], d["dk"], d["dv"], d["K"]) == (
        2048, 16, 32, 128, 128, 4)
    assert (d["H"], d["KVH"], d["hd"], d["rot"], d["theta"]) == (
        16, 2, 256, 64, 1e7)
    assert (d["router"], d["top_k"], d["F"], d["Fs"], d["held"],
            d["first"], d["V"]) == (512, 10, 512, 512, 256, 0, 75968)
    assert d["pattern"] == "LELELEFE"
    for key in ("norm", "shared_expert_gate", "attention_output_gate",
                "rotary", "qk_l2norm_and_scale", "conv_bias",
                "initialisation", "precision.state", "w_qkvz_layout"):
        assert key in config["assumed"], key
    assert "mtp" in config["omitted"] and "two chips" in config["deployment"]
    # 3,677,613,120 parameters: 7.36 GB in bfloat16
    total = (flops_qwen3_next.dense_matmul_params(d)
             - d["D"] * d["V"] + 2 * d["D"] * d["V"]
             + 4 * d["held"] * flops_qwen3_next.expert_params(d)
             + 3 * (32768 + 192) + 512 + 8 * 2048 + 2048)
    assert total == 3677613120
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
    requests = traffic_gen.serve_requests(mix, 1, seconds, 75968)
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
    c = info["device_counters"]
    ahead = c["decode_moe_steps_total"] - info["decode_steps"]
    assert 0 <= ahead <= 64
    assert (c["moe_steps_total"] - c["decode_moe_steps_total"]
            == info["prefills"])
    assert 0 < c["experts_active_total"] <= c["expert_assignments_held_total"]
    assert info["host_syncs"] <= info["decode_steps"] + line["attempted"]
    # counters read on the CPU; a share of a peak or of a trace does not
    assert "tokens_per_active_expert" in line["metrics"]
    assert not set(NEW) & set(line["metrics"])
    assert all(m["value"] is None for m in line["metrics"].values())


CONTROLS = ("int8", "routed_dropped", "experts_shifted", "delta_dropped",
            "gate_dropped")


def test_every_control_and_planted_fault_comes_out_not_correct(capsys):
    line, err = _run(capsys, "--control", ",".join(CONTROLS), seed=5,
                     seconds="4")
    assert "FAILED" in err and line["correct"] is False
    gap = line["compared"]["served_token_gap_mean"]
    assert gap["value"] > gap["limit"]
    assert line["info"]["program_served_token_gap_mean"] <= gap["limit"]
    assert "served_token_gap" not in line["compared"]
    controls = line["info"]["controls"]
    assert list(controls) == list(CONTROLS)
    for name, read in controls.items():
        assert "served_token_gap_mean" in read["not_correct_by"], name
    assert controls["int8"]["served_token_gap_mean"] == gap["value"]


def test_planted_faults_change_their_own_part_only():
    """Each fault of the reference moves the logits; ``experts_shifted``
    is the share a wrong ``experts_held_first`` would compute."""
    d = reference.dims_of(manifest.cell(CELL, dry_run=True)["config"])
    weights = reference.init_weights(d, 3, jnp.float32)
    rows = np.arange(24, dtype=np.int32).reshape(2, 12) % d["V"]
    plain = reference.forward_logits(weights, rows, d)
    for fault in reference.FAULTS:
        moved = reference.forward_logits(weights, rows, d, fault)
        assert not jnp.array_equal(plain, moved), fault
    shifted = reference.forward_logits(weights, rows, d, "experts_shifted")
    other = dict(d, first=d["first"] + d["held"])
    assert jnp.array_equal(
        shifted, reference.forward_logits(weights, rows, other))
    with pytest.raises(ValueError):
        reference.forward_logits(weights, rows, d, "int4")


def test_state_lost_at_install_comes_out_not_correct(capsys):
    """The slot keeps the state it had (here none) instead of the
    prefill's: every token after the first is picked from a wrong
    state."""
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


def _hand_run(active_a_step=640.0, steps=1000, live=16.0, kernel_ms=0.3,
              expert_ms=2.4):
    """A window of ``steps`` decode steps with ``live`` slots each; the
    trace holds twelve runs of the decode program, each with three
    delta-rule calls of ``kernel_ms`` / 3 and four expert calls of
    ``expert_ms`` / 4, and a prefill run whose calls do not count."""
    runs, ops = [], []
    for i in range(12):
        t0 = int(i * 8e6)
        runs.append(("jit_paged_decode_step(77)", t0, int(6e6)))
        for j in range(3):
            ops.append((f"%gated_delta_decode.{j} = (f32[32,32,128]) "
                        "custom-call(%a, %b)", t0 + 10 + j,
                        int(kernel_ms / 3 * 1e6)))
        for j in range(4):
            ops.append((f"%held_experts_ffn.{j} = f32[32,2048] "
                        "custom-call(%a)", t0 + 100 + j,
                        int(expert_ms / 4 * 1e6)))
    runs.append(("jit_paged_prefill(5)", int(200e6), int(9e6)))
    ops.append(("%held_experts_ffn.9 = f32[128,2048] custom-call(%a)",
                int(201e6), int(5e6)))
    spans = [{"prompt_len": 100, "lo": 1, "hi": 1 + steps, "prefill": False}
             for _ in range(int(live))]
    return {
        "dims": _dims(), "window_s": 14.0, "notes": {},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": {"modules": runs, "ops": ops, "host": []},
        "stamped": {"spans": spans},
        "counters": {"decode_steps": steps, "occupancy_sum": live * steps,
                     "slots": 32, "decode_moe_steps_total": steps,
                     "decode_experts_active_total": active_a_step * steps,
                     "expert_assignments_held_total": 4 * 80.0 * steps,
                     "experts_active_total": active_a_step * steps}}


def test_deltanet_roofline_counts_the_live_slots_state():
    read, params = _reader("deltanet_decode_roofline")
    run = _hand_run()
    share = read(run, params)
    note = run["notes"]["deltanet_decode_roofline"]
    assert note["runs"] == 10 and abs(note["kernel_ms_a_run"] - 0.3) < 1e-5
    want = flops_qwen3_next.deltanet_decode_bytes(_dims(), 16.0)
    # 16 live slots x 3 layers x 2 x 2 MiB: some 0.2 GB a step
    assert 0.2e9 < want < 0.21e9
    assert abs(share - 100 * want / 819e9 / (note["kernel_ms_a_run"] / 1e3)
               ) < 1e-6
    assert 0 < share < 100
    # the whole state of 32 slots at that time would read over 100 %
    assert read(_hand_run(live=32.0, kernel_ms=0.3), params) > 100
    for broken in ({"trace": None}, {"peaks": None}):
        assert read({**_hand_run(), **broken}, params) is None
    silent = _hand_run()
    silent["trace"]["ops"] = [o for o in silent["trace"]["ops"]
                              if "gated_delta" not in o[0]]
    assert read(silent, params) is None          # the parent: no kernel


def test_expert_roofline_counts_the_experts_that_met_a_token():
    read, params = _reader("expert_ffn_roofline")
    run = _hand_run(active_a_step=280.0, expert_ms=2.4)
    share = read(run, params)
    note = run["notes"]["expert_ffn_roofline"]
    # the prefill's call is not under the decode program
    assert abs(note["kernel_ms_a_run"] - 2.4) < 1e-6
    assert flops_qwen3_next.expert_bytes(_dims()) == 6291456
    assert abs(share - 100 * 280 * 6291456 / 819e9 / 2.4e-3) < 1e-6
    assert 50 < share < 100
    silent = _hand_run()
    del silent["counters"]["decode_moe_steps_total"]
    assert read(silent, params) is None


def test_mfu_counts_assignments_held_and_the_delta_rule():
    read, params = _reader("deltanet_serve_mfu_pct")
    d = _dims()
    run = _hand_run()
    share = read(run, params)
    tokens = 16 * 1000
    positions = 16 * (1000 * 100 + (1 + 1000) * 1000 / 2.0)
    want = flops_qwen3_next.serve_flops(d, tokens, 4 * 80.0 * 1000,
                                        positions)
    assert abs(share - 100 * want / (14.0 * 197e12)) < 1e-9
    assert 0 < share < 100
    run2 = _hand_run()
    run2["stamped"]["spans"][0].update(lo=0, prefill=True)
    assert read(run2, params) > share
    silent = _hand_run()
    del silent["counters"]["expert_assignments_held_total"]
    assert read(silent, params) is None


# -- the program's copy of the counts -----------------------------------


@pytest.mark.parametrize("dry", [False, True], ids=["published", "rehearsal"])
def test_program_and_benchmark_count_alike(dry):
    from kubeflow_rm_tpu.utils import flops as program
    from perf.kinds import serve_qwen3_next
    config = manifest.cell(CELL, dry_run=dry)["config"]
    d, cfg = (reference.dims_of(config),
              serve_qwen3_next.qwen3_next_config(config))
    assert cfg.pattern == d["pattern"]
    assert (program.qwen3_next_dense_matmul_params(cfg)
            == flops_qwen3_next.dense_matmul_params(d))
    assert (program.qwen3_next_expert_params(cfg)
            == flops_qwen3_next.expert_params(d))
    assert (program.qwen3_next_state_flops_per_token(cfg)
            == flops_qwen3_next.state_flops_per_token(d))
    assert (program.qwen3_next_serve_flops(cfg, 1e4, 3e4, 5e6)
            == flops_qwen3_next.serve_flops(d, 1e4, 3e4, 5e6))
    assert (program.qwen3_next_deltanet_decode_bytes(cfg, 18.0)
            == flops_qwen3_next.deltanet_decode_bytes(d, 18.0))


def test_the_config_builder_holds_the_assumptions_to_the_program():
    from perf.kinds import serve_qwen3_next
    config = dict(manifest.cell(CELL)["config"], conv_bias=True)
    with pytest.raises(ValueError, match="conv_bias"):
        serve_qwen3_next.qwen3_next_config(config)
