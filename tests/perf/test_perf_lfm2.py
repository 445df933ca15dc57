"""The cell ``serve-chat-wide`` at the rehearsal's size on the CPU:
the manifest, the configuration against the catalog row, the gate that
holds its ``assumed`` to the program, the mix's sizes and its quiet
close, the kind end to end (``correct`` true; false under the int8
control and the reference's planted faults), ``perf/flops_lfm2.py``'s
counts at the published widths, and both new per-layer readers on a
hand-made run."""

import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import check_manifest, flops_lfm2, manifest, traffic_gen  # noqa: E402,E501
from perf import reference_lfm2 as reference  # noqa: E402
from perf import run as perf_run  # noqa: E402

CELL = "serve-chat-wide"
NEW = ("shortconv_serve_mfu_pct", "shortconv_decode_roofline")
SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
#: the catalog row's ``layer_types``, as published
PUBLISHED_TYPES = (["conv", "conv", "full_attention"]
                   + ["conv", "conv", "conv", "full_attention"] * 4
                   + ["conv", "conv", "full_attention", "conv", "conv"])
#: every other number of the catalog row's ``config``
ROW = {"conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
       "intermediate_size": 7168, "max_position_embeddings": 128000,
       "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
       "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
       "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
       "num_key_value_heads": 8, "rope_theta": 1000000,
       "routed_scaling_factor": 1, "use_expert_bias": True,
       "vocab_size": 65536}


def _run(capsys, *extra, seed=2 ** 31 + 101, seconds="3"):
    rc = perf_run.main(["--workload", CELL, "--seed", str(seed),
                        "--seconds", seconds, "--cpu-dry-run", *extra])
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def _reader(name):
    base = manifest.PERF / "metrics" / name
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), base.with_name(name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read, json.loads(base.with_name(name + ".json").read_text())


def _dims():
    return reference.dims_of(manifest.cell(CELL)["config"])


def test_manifest_holds_the_cell_and_its_readers():
    assert check_manifest.check() == []
    cell = manifest.cell(CELL)
    assert cell["traffic"]["kind"] == "serve_lfm2" and cell["chips"] == 1
    assert (manifest.PERF / "kinds" / "serve_lfm2.py").is_file()
    names = {m["name"] for m in cell["per_layer"]}
    assert set(NEW) | {"tokens_per_active_expert", "batch_occupancy",
                       "decode_step_ms", "device_idle_pct.serve",
                       "gateway_queue_ms_p95"} <= names
    assert {f"step_idle_ms.{s}" for s in (
        "admit", "pick", "dispatch", "scatter", "gateway",
        "unattributed")} <= names
    # the other families' counts are not read here
    assert not {"serve_mfu_pct", "paged_decode_roofline",
                "hybrid_serve_mfu_pct", "hybrid_decode_roofline",
                "deltanet_decode_roofline", "window_serve_mfu_pct",
                "window_decode_roofline", "expert_ffn_roofline"} & names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "req_latency_p95_ms", "serve_tok_s", "setup_s"}
    for m in manifest.load()["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"


def test_configuration_is_the_catalog_row_cut_as_stated():
    config = manifest.cell(CELL)["config"]
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "lfm2-8b-a1b-serve-l16")
    assert entry["source"] == SOURCE
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    assert config["published"] == {"num_hidden_layers": 24,
                                   "layer_types": PUBLISHED_TYPES}
    for key, value in ROW.items():
        assert config[key] == value, key
    assert config["num_hidden_layers"] == 16
    assert config["layer_types"] == PUBLISHED_TYPES[:16]
    d = _dims()
    assert d["pattern"] == "CDCDAECECECEAECECECEAECECECEAECE"
    assert (d["D"], d["H"], d["KVH"], d["hd"], d["K"]) == (2048, 32, 8, 64,
                                                           3)
    assert (d["Fd"], d["F"], d["E"], d["top_k"], d["V"]) == (
        7168, 1792, 32, 4, 65536)
    for key in ("conv_chunk_order", "norm_gain", "rope", "route_norm_eps",
                "tie_word_embeddings", "initialisation"):
        assert key in config["assumed"], key
    assert "two pipeline stages" in config["deployment"]
    sv = config["serving"]
    assert (sv["slots"], sv["slot_len"]) == (64, 2048)
    assert sv["num_blocks"] == 2 + sv["slots"] * sv["slot_len"] // sv[
        "block_size"]


def test_the_config_maker_holds_the_assumptions_to_the_program():
    from perf.kinds import serve_lfm2
    config = manifest.cell(CELL)["config"]
    cfg = serve_lfm2.lfm2_config(config)
    assert cfg.pattern == _dims()["pattern"]
    assert cfg.experts_held == (0, 32) and cfg.route_eps == 1e-6
    assert cfg.head_dim == 64
    for key, value in (("conv_chunk_order", ["x", "B", "C"]),
                       ("norm_gain", "1 + w"), ("route_norm_eps", 1e-20),
                       ("tie_word_embeddings", False),
                       ("num_shared_experts", 1)):
        with pytest.raises(ValueError, match=key):
            serve_lfm2.lfm2_config(dict(config, **{key: value}))


def test_the_mix_keeps_its_stated_medians_and_limits():
    """LMSYS-Chat-1M's means by the chat mix's log-normals; prompts
    clipped at 1,024 and prompt and answer inside a 2,048 slot."""
    mix = manifest.cell(CELL)["traffic"]
    config = manifest.cell(CELL)["config"]
    for key, mean in (("prompt_tokens", 69.5), ("answer_tokens", 214.5)):
        sizes = traffic_gen.quantile_sizes(mix[key], 4001)
        assert abs(np.mean(sizes) / mean - 1) < 0.05, key
    assert mix["prompt_tokens"]["max"] == 1024
    assert (mix["prompt_tokens"]["max"] + mix["answer_tokens"]["max"]
            <= config["serving"]["slot_len"])
    why = next(w["why"] for w in manifest.load()["workloads"]
               if w["name"] == CELL)
    assert f"{mix['arrivals']['rate_per_s']} requests/s" in why


def test_order_keeps_the_windows_close_quiet():
    """The mix's order lets nobody in during the window's last seconds
    and leaves no more in flight than its ``close`` block states."""
    mix = manifest.cell(CELL)["traffic"]
    seconds = float(manifest.load()["run_seconds"])
    requests = traffic_gen.serve_requests(mix, 1, seconds, 65536)
    cut = traffic_gen.in_flight_at_close(requests, seconds, mix["close"])
    assert len(cut) <= mix["close"]["in_flight_max"]
    assert max(r["due_s"] for r in requests) < seconds - mix["close"][
        "quiet_s"]
    assert max(t for r in requests for t in r["prompt"]) < 65536


def test_cell_rehearsal_comes_out_correct(capsys):
    line, _err = _run(capsys, "--trace", "1")
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}
    info = line["info"]
    assert info["rows_compared"] > info["requests_compared"] > 1
    assert info["prefix_hits_refused"] == 0
    c = info["device_counters"]
    assert 0 < c["decode_full_positions_total"] < c["full_positions_total"]
    assert info["recurrent_state_bytes"] > 0 and info["kv_pool_bytes"] > 0
    # the second witness, the reference at bfloat16, reads the rounding
    witness = info["witness_bf16"]["logit_err_row_median"]
    assert info["readings"]["logit_err_row_median"] < 1e-5 < witness < 0.05
    # float32 on both sides: the reference runs the program's choice
    # and would have made it itself
    assert info["readings"]["route_gap_mean"] == 0
    assert info["free_choice"]["argmax_agree"] == 1


#: the limit each control falls by: a choice that ignores the bias runs
#: other experts with the right arithmetic, so it is the routing's
FALLS_BY = {"int8": "logit_err_row_median",
            "tail_dropped": "logit_err_row_median",
            "bias_ignored": "route_gap_mean"}


@pytest.mark.parametrize("control", ["int8", "tail_dropped",
                                     "bias_ignored"])
def test_control_and_planted_faults_come_out_not_correct(capsys, control):
    """The int8 control and two planted faults, a convolution that
    forgets its tail and a choice that ignores ``expert_bias``, each in
    the program's place: ``correct`` false by the same comparison."""
    line, err = _run(capsys, "--control", control, seed=2 ** 31 + 7)
    assert line["correct"] is False
    falls = [ln for ln in err.splitlines() if ln.startswith("compared ")
             and ln.endswith("FAILED")]
    assert any(ln.startswith(f"compared {FALLS_BY[control]} ")
               for ln in falls), err[-2000:]
    assert FALLS_BY[control] in line["info"]["controls"][control][
        "not_correct_by"]
    assert line["info"]["program_logit_err_row_median"] < 1e-5
    assert line["info"]["program_route_gap_mean"] == 0


# -- the counts ----------------------------------------------------------


def test_flops_and_bytes_at_the_published_widths():
    d = _dims()
    # an expert's three matrices: 22.02 MB in bfloat16
    assert flops_lfm2.expert_bytes(d) == 3 * 2048 * 1792 * 2 == 22020096
    # every weight of the cut: 10.80 GB (5,399,129,024 parameters, the
    # router and its bias in float32), the experts 9.87 GB of it
    experts = 14 * 32 * flops_lfm2.expert_bytes(d)
    assert flops_lfm2.weight_bytes(d) + experts == 10800093952
    assert abs(experts / 9.865e9 - 1) < 1e-3
    # a position's key and value in one attention layer: 2 KB
    assert flops_lfm2.kv_bytes_per_position(d) == 2048
    # a conv layer's matrices 16.78 M, an attention layer's 10.49 M, a
    # dense MLP's 44.04 M, a router's 65.5 k, the tied head 134.2 M
    assert flops_lfm2.dense_matmul_params(d) == (
        12 * 16777216 + 4 * 10485760 + 2 * 44040192 + 14 * 65536
        + 2048 * 65536)
    assert flops_lfm2.conv_flops_per_token(d) == 2 * 3 * 2048
    one = flops_lfm2.serve_flops(d, 1, 1, 0, 0)
    assert flops_lfm2.serve_flops(d, 1, 1, 2, 10) == (
        one + 2 * 2 * 11010048 + 4 * 32 * 64 * 4 * 10)
    assert one - flops_lfm2.serve_flops(d, 1, 0, 0, 0) == 2 * 2048 * 65536
    # a full engine meeting every expert, its tails and 20 k keys
    step = flops_lfm2.decode_step_bytes(d, 448, 64, 20000)
    assert step == (10800093952 + 64 * 12 * 2 * 2 * 2048 * 2
                    + 2048 * 4 * 20000)


def test_fed_follows_the_stamped_tokens():
    spans = [{"prompt_len": 70, "lo": 0, "hi": 3, "prefill": True},
             {"prompt_len": 100, "lo": 5, "hi": 7, "prefill": False}]
    f = flops_lfm2.fed(spans)
    assert f == {"tokens": 70 + 2 + 2, "heads": 1 + 2 + 2}


# -- the new readers, on a hand-made run ---------------------------------


def _hand_run(steps=1000, live=40.0, pairs=12000.0, step_ms=24.0):
    """A window of ``steps`` decode steps of ``live`` slots attending
    ``pairs`` keys a step; the trace holds twelve decode runs of
    ``step_ms`` each and a prefill run."""
    runs = [("jit_paged_decode_step(77)", int(i * 30e6), int(step_ms * 1e6))
            for i in range(12)]
    runs.append(("jit_paged_prefill(5)", int(400e6), int(15e6)))
    spans = [{"prompt_len": 70, "lo": 1, "hi": 1 + steps, "prefill": False}
             for _ in range(int(live))]
    return {
        "dims": _dims(), "window_s": 51.0, "notes": {},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": {"modules": runs, "ops": [], "host": []},
        "stamped": {"spans": spans},
        "counters": {"decode_steps": steps, "occupancy_sum": live * steps,
                     "decode_moe_steps_total": steps,
                     "decode_experts_active_total": 448.0 * steps,
                     "expert_assignments_held_total": 14 * 4 * live * steps,
                     "experts_active_total": 448.0 * steps,
                     "full_positions_total": pairs * steps,
                     "decode_full_positions_total": pairs * steps}}


def test_decode_roofline_reads_the_program_and_the_counters():
    read, params = _reader("shortconv_decode_roofline")
    run = _hand_run()
    share = read(run, params)
    note = run["notes"]["shortconv_decode_roofline"]
    assert note["runs"] == 10 and abs(note["device_ms_a_run"] - 24.0) < 1e-6
    want = flops_lfm2.decode_step_bytes(_dims(), 448, 40, 12000)
    assert abs(share - 100 * want / 819e9 / 24e-3) < 1e-9
    assert 50 < share < 100
    for broken in ({"trace": None}, {"peaks": None}):
        assert read({**_hand_run(), **broken}, params) is None
    parent = _hand_run()
    del parent["counters"]["decode_full_positions_total"]
    assert read(parent, params) is None         # no counter: silent


def test_mfu_counts_the_taps_the_pairs_and_the_experts_held():
    read, params = _reader("shortconv_serve_mfu_pct")
    d = _dims()
    run = _hand_run()
    share = read(run, params)
    f = flops_lfm2.fed(run["stamped"]["spans"])
    want = flops_lfm2.serve_flops(d, f["tokens"], f["heads"],
                                  14 * 4 * 40.0 * 1000, 12000.0 * 1000)
    assert abs(share - 100 * want / (51.0 * 197e12)) < 1e-9
    assert 0 < share < 100
    silent = _hand_run()
    del silent["counters"]["full_positions_total"]
    assert read(silent, params) is None
