"""The cell ``serve-longdoc-steady`` at the rehearsal's size on the CPU:
the manifest, the configuration against the catalog row, the mix's
sizes and its quiet close, the kind end to end (``correct`` true;
false under the int8 control and each planted fault of the
reference), ``perf/flops_afmoe.py``'s counts on hand-made numbers, and
each new per-layer reader on a hand-made run."""

import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from perf import check_manifest, flops_afmoe, flops_qwen3_next  # noqa: E402
from perf import manifest, traffic_gen  # noqa: E402
from perf import reference_afmoe as reference  # noqa: E402
from perf import run as perf_run  # noqa: E402
from perf.kinds import serve_afmoe  # noqa: E402

CELL = "serve-longdoc-steady"
NEW = ("window_serve_mfu_pct", "window_decode_roofline",
       "window_prefill_roofline")
SOURCE = "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"


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
    assert cell["traffic"]["kind"] == "serve_afmoe" and cell["chips"] == 1
    assert (manifest.PERF / "kinds" / "serve_afmoe.py").is_file()
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
                "deltanet_decode_roofline", "deltanet_serve_mfu_pct"} & names
    assert {m["name"] for m in cell["end_to_end"]} == {
        "req_latency_p95_ms", "serve_tok_s", "setup_s"}
    for m in manifest.load()["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"


def test_configuration_is_the_catalog_row_cut_as_stated():
    config = manifest.cell(CELL)["config"]
    entry = next(c for c in manifest.load()["configs"]
                 if c["name"] == "trinity-mini-serve-l16-e16")
    assert entry["source"] == SOURCE
    published = {"num_hidden_layers": 32, "num_experts": 128,
                 "layer_types": (["sliding_attention"] * 3
                                 + ["full_attention"]) * 8}
    assert sorted(entry["reduced"]) == sorted(published)
    assert config["published"] == published
    assert config["num_hidden_layers"] == 16 and config["num_experts"] == 16
    assert config["layer_types"] == published["layer_types"][:16]
    d = _dims()
    assert (d["D"], d["H"], d["KVH"], d["hd"], d["window"]) == (
        2048, 32, 4, 128, 2048)
    assert (d["Fd"], d["F"], d["Fs"], d["top_k"], d["router"], d["V"]) == (
        6144, 1024, 1024, 8, 128, 200192)
    assert (d["held"], d["first"], d["scale"]) == (16, 0, 2.826)
    assert d["pattern"] == "WDWDWEFE" + "WEWEWEFE" * 3
    for key in ("norm", "full_attention_positions", "window",
                "attention_output_gate", "qk_norm", "embedding_scale",
                "routing", "shared_expert_gate", "initialisation"):
        assert key in config["assumed"], key
    assert "eight chips" in config["deployment"]
    # 2,832,867,072 parameters: 5.67 GB in bfloat16
    n = flops_afmoe.kinds(d)
    total = (flops_afmoe.dense_matmul_params(d) + d["D"] * d["V"]
             + n["E"] * (d["held"] * flops_afmoe.expert_params(d)
                         + d["router"])
             + (n["W"] + n["F"]) * (2 * d["D"] + 2 * d["hd"])
             + (n["D"] + n["E"]) * 2 * d["D"] + d["D"])
    assert total == 2832867072
    sv = config["serving"]
    assert sv["num_blocks"] == 2 + sv["slots"] * sv["slot_len"] // sv[
        "block_size"]


def test_the_mix_keeps_its_stated_medians_and_limits():
    """Every prompt past the window and inside the 8,192 bucket; the
    sizes a window gets keep the mix's medians within 3 %."""
    mix = manifest.cell(CELL)["traffic"]
    config = manifest.cell(CELL)["config"]
    for key in ("prompt_tokens", "answer_tokens"):
        sizes = traffic_gen.quantile_sizes(mix[key], 2001)
        assert abs(np.median(sizes) / mix[key]["median"] - 1) < 0.03
    assert mix["prompt_tokens"]["min"] > config["sliding_window"]
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
    requests = traffic_gen.serve_requests(mix, 1, seconds, 200192)
    cut = traffic_gen.in_flight_at_close(requests, seconds, mix["close"])
    assert 0 < len(cut) <= mix["close"]["in_flight_max"]
    assert max(r["due_s"] for r in requests) < seconds - mix["close"][
        "quiet_s"]


def test_cell_rehearsal_comes_out_correct(capsys):
    line, err = _run(capsys, "--trace", "1")
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["compared"]["compiles_in_window"] == {"value": 0, "limit": 0}
    info = line["info"]
    assert info["rows_compared"] > info["requests_compared"] > 1
    assert info["prefix_hits_refused"] == 0
    c = info["device_counters"]
    assert 0 < c["decode_window_positions_total"] < c[
        "decode_full_positions_total"]
    assert info["window_state_bytes"] > 0 and info["kv_pool_bytes"] > 0
    # the compared requests sit across the engine, others beside them
    slots = manifest.cell(CELL, dry_run=True)["config"]["serving"]["slots"]
    assert info["replay_slots"]["seats"] == [0, 2, 4, 6] == (
        serve_afmoe.seats(4, slots))
    assert info["replay_slots"]["live_mean"] > 4
    # the second witness, the reference at bfloat16, reads the rounding
    witness = info["witness_bf16"]["logit_err_row_median"]
    assert info["readings"]["logit_err_row_median"] < 1e-5 < witness < 0.05
    assert len(info["routing"]["worst_rows"]) == min(12, info[
        "rows_compared"])


@pytest.mark.parametrize("control", ["int8", "window_dropped",
                                     "routed_dropped"])
def test_control_and_planted_faults_come_out_not_correct(capsys, control):
    line, err = _run(capsys, "--control", control, seed=2 ** 31 + 7)
    assert line["correct"] is False
    assert "compared logit_err_row_median" in err and "FAILED" in err
    assert line["info"]["program_logit_err_row_median"] < 1e-5


def test_the_config_maker_holds_the_assumptions_to_the_program():
    from perf.kinds import serve_afmoe
    config = manifest.cell(CELL)["config"]
    cfg = serve_afmoe.afmoe_config(config)
    assert cfg.pattern == _dims()["pattern"]
    assert cfg.experts_held == (0, 16) and cfg.n_routed_experts == 128
    for key, value in (("full_attention_positions", "rope"),
                       ("shared_expert_gate", "sigmoid")):
        with pytest.raises(ValueError, match=key):
            serve_afmoe.afmoe_config(dict(config, **{key: value}))


def test_routing_reads_the_flips_row_by_row():
    """A row flips where the witness's held choice differs from the
    reference's in some expert part; the rows that flipped and those
    that did not are read apart, the worst listed one by one."""
    from perf.kinds.serve_afmoe import routing
    ref = [(np.zeros((2, 6), np.int32), np.full((2, 6), 0.5, np.float32)),
           (np.ones((2, 6), np.int32), np.full((2, 6), 0.2, np.float32))]
    wit = [(r[0].copy(), r[1]) for r in ref]
    wit[1][0][1, 4] = 3                 # request 1, column 4, part 2
    wit[0][0][0, 2] = 5                 # request 0, column 2, part 1
    ref[0][1][1, 4] = 1e-4
    at = [(0, 1), (0, 2), (0, 3), (1, 3), (1, 4), (1, 5)]
    own = np.array([0.01, 0.2, 0.01, 0.012, 0.3, 0.011])
    witness = np.array([0.011, 0.15, 0.01, 0.01, 0.25, 0.012])
    first = np.array([True, False, False, True, False, False])
    r = routing(own, witness, ref, wit, at, first)
    assert r["flipped_share"] == 2 / 6
    assert r["flipped"]["rows"] == 2 and r["kept"]["rows"] == 4
    assert r["flipped"]["program_median"] == 0.25
    assert abs(r["kept"]["program_median"] - 0.0105) < 1e-12
    assert r["program_tail_flipped_share"] == 1.0
    assert r["prefill_rows_flipped"] == 0
    # the least lead: request 1's column 4 (1e-4) leads the rows, the
    # others tie at 0.2, so the near ties are the ties at the quantile
    near = routing(own, witness, ref, wit, at, first, near=0.0)
    assert near["near_tie"]["rows"] == 1
    assert near["near_tie"]["program_median"] == 0.3
    assert near["program_tail_near_tie_share"] == 1.0
    assert near["margin_rank_corr"]["program"] < 0
    assert r["worst_rows"][0] == [1, 4, False, 0.3, 0.25, 1, 0.0001]
    assert r["worst_rows"][1][:2] == [0, 2]


def test_route_of_marks_the_held_choice_and_its_lead():
    """Experts 2-4 held of 8, two chosen a column: the held ones chosen
    as bits from the first held, and the least lead a choice has over a
    key it beat where one of the two is held."""
    import jax.numpy as jnp
    d = {"first": 2, "held": 3}
    z = jnp.asarray([[0.9, 0.1, 0.8, 0.2, 0.3, 0.0, 0.1, 0.75],
                     [0.9, 0.8, 0.1, 0.2, 0.3, 0.0, 0.1, 0.05]])
    idx = jnp.asarray([[0, 2], [0, 1]])
    bits, margin = reference._route_of(z, idx, d)
    # column 0: held expert 2 chosen (bit 0); it leads unchosen 7 by .05
    # column 1: none held chosen; the least chosen leads held 4 by .5
    assert bits.tolist() == [1, 0]
    assert np.allclose(margin, [0.05, 0.5])


def test_readings_are_the_rows_relative_error():
    from perf.kinds.serve_afmoe import readings
    want = np.array([[3.0, 4.0], [0.0, 1.0]])
    got = want + np.array([[0.5, 0.0], [0.0, 0.1]])
    r = readings(got, want)
    assert abs(r["logit_err_rms"] - np.sqrt(0.26 / 26.0)) < 1e-12
    assert abs(r["logit_err_row_max"] - 0.1) < 1e-12


# -- the counts ----------------------------------------------------------


def test_flops_count_what_each_layer_reaches():
    d = _dims()
    assert flops_afmoe.window_pairs(3, 8) == 6
    assert flops_afmoe.window_pairs(10, 4) == 4 * 5 // 2 + 6 * 4
    assert flops_afmoe.window_pairs(8192, 2048) == (
        sum(min(p + 1, 2048) for p in range(8192)))
    # a layer's attention 27.26 M, a dense MLP 37.7 M, an expert layer's
    # router and shared expert 6.55 M, the head 410 M
    assert flops_afmoe.dense_matmul_params(d) == (
        16 * 27262976 + 2 * 37748736 + 14 * (262144 + 6291456)
        + 2048 * 200192)
    # 2.03 GB of weights outside the experts a decode step
    assert abs(2 * flops_afmoe.dense_matmul_params(d) / 2.03e9 - 1) < 0.01
    assert flops_afmoe.expert_params(d) == 6291456
    one = flops_afmoe.serve_flops(d, 1, 1, 0, 0, 0)
    assert flops_afmoe.serve_flops(d, 1, 1, 2, 10, 3) == (
        one + 2 * 2 * 6291456 + 4 * 32 * 128 * (4 * 10 + 12 * 3))
    # a prompt token that the head skips: the head's 410 M less
    assert one - flops_afmoe.serve_flops(d, 1, 0, 0, 0, 0) == (
        2 * 2048 * 200192)
    # 2 KB a position a layer
    assert flops_afmoe.decode_attention_bytes(d, 1, 0) == 4 * 2048
    assert flops_afmoe.decode_attention_bytes(d, 0, 1) == 12 * 2048
    f_w, _ = flops_afmoe.prefill_attention_cost(d, 4096, True)
    f_f, _ = flops_afmoe.prefill_attention_cost(d, 4096, False)
    assert f_w == 4 * 32 * 128 * flops_afmoe.window_pairs(4096, 2048)
    assert f_f == 4 * 32 * 128 * 4096 * 4097 / 2


def test_attended_follows_the_stamped_tokens():
    spans = [{"prompt_len": 3000, "lo": 0, "hi": 3, "prefill": True},
             {"prompt_len": 100, "lo": 5, "hi": 7, "prefill": False}]
    a = flops_afmoe.attended(spans, 2048)
    assert a["tokens"] == 3000 + 2 + 2
    assert a["heads"] == 1 + 2 + 2
    assert a["full_positions"] == (3000 * 3001 // 2 + 3001 + 3002
                                   + 105 + 106)
    assert a["window_positions"] == (flops_afmoe.window_pairs(3000, 2048)
                                     + 2048 + 2048 + 105 + 106)


# -- the new readers, on hand-made runs ---------------------------------


SHIFT = 3_000_000
PROMPTS = (5000, 7000, 6000)


def _hand_run(steps=1000, live=16.0, full=6300.0, window=2048.0,
              attn_ms=2.4, flash_ms=(1.3, 3.5), prefill_runs=2):
    """A window of ``steps`` decode steps of ``live`` slots at a context
    of ``full``; the trace holds twelve decode runs, each with four
    paged and twelve window calls summing to ``attn_ms`` and fourteen
    expert calls, and ``prefill_runs`` prefill runs of the 8,192 bucket
    whose window and full flash calls take ``flash_ms`` each, plus one
    the slice cut."""
    runs, ops = [], []
    for i in range(12):
        t0 = int(i * 10e6)
        runs.append(("jit_paged_decode_step(77)", t0, int(9e6)))
        for j, kind in enumerate("WWWFWWWFWWWFWWWF"):
            name = ("window_decode_attention" if kind == "W"
                    else "paged_decode_attention")
            ops.append((f"%{name}.{j} = bf16[32,32,128] custom-call(%a), "
                        'custom_call_target="tpu_custom_call"',
                        t0 + 10 + j, int(attn_ms / 16 * 1e6)))
        for j in range(14):
            ops.append((f"%held_experts_ffn.{j} = f32[32,2048] "
                        "custom-call(%a)", t0 + 100 + j, int(0.2e6)))
    kinds = [c for c in _dims()["pattern"] if c in "WF"]
    for r in range(prefill_runs + 1):
        t0 = int(200e6 + r * 100e6)
        runs.append(("jit_paged_prefill(5)", t0, int(90e6)))
        calls = kinds if r < prefill_runs else kinds[:5]   # the cut one
        for j, kind in enumerate(calls):
            ms = flash_ms[0] if kind == "W" else flash_ms[1]
            ops.append((f"%custom-call.{j} = (bf16[32,8192,128]{{2,1,0}}, "
                        "f32[32,8,8192]{2,1,0}) custom-call(%q, %k, %v), "
                        'custom_call_target="tpu_custom_call"',
                        t0 + 10 + int(j * 4e6), int(ms * 1e6)))
        ops.append(("%custom-call.99 = bf16[65536,1024]{1,0} custom-call("
                    '%x), custom_call_target="tpu_custom_call"',
                    t0 + int(80e6), int(1e6)))
    spans = [{"prompt_len": 5000, "lo": 1, "hi": 1 + steps, "prefill": False}
             for _ in range(int(live))]
    # the host's clock runs SHIFT ns ahead of the device's, and the
    # engine's stamps (perf_counter) 99 s behind the host's: each decode
    # run is launched half a millisecond before it starts, and each
    # prefill run's request admitted two before it starts, prompts of
    # PROMPTS; one more request comes after the slice
    host = [("engine.dispatch", t0 + SHIFT - int(0.5e6), int(0.4e6))
            for name, t0, _d in runs if "decode" in name]
    host.append(("perf.clock_mark", 5000, 1000))
    mark_s = 5000 / 1e9 - 99.0
    prefills = sorted(t0 for name, t0, _d in runs if "prefill" in name)
    requests = [{"prompt_len": n, "timeline": {
        "t_admitted": (t0 + SHIFT - int(2e6)) / 1e9 - 99.0}}
        for n, t0 in zip(PROMPTS, prefills)]
    requests.append({"prompt_len": 6000, "timeline": {
        "t_admitted": 900e6 / 1e9 - 99.0}})
    return {
        "dims": _dims(), "window_s": 51.0, "notes": {},
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": {"modules": runs, "ops": ops, "host": host},
        "requests": requests, "clock_mark_s": mark_s,
        "stamped": {"spans": spans},
        "counters": {"decode_steps": steps, "occupancy_sum": live * steps,
                     "decode_moe_steps_total": steps,
                     "decode_experts_active_total": 140.0 * steps,
                     "expert_assignments_held_total": 14 * 16.0 * steps,
                     "experts_active_total": 140.0 * steps,
                     "decode_full_positions_total": live * full * steps,
                     "decode_window_positions_total": live * window * steps}}


def test_decode_roofline_reads_both_kernels_and_the_counters():
    read, params = _reader("window_decode_roofline")
    run = _hand_run()
    share = read(run, params)
    note = run["notes"]["window_decode_roofline"]
    assert note["runs"] == 10 and abs(note["kernels_ms_a_run"] - 2.4) < 1e-3
    want = 2048 * 16 * (4 * 6300 + 12 * 2048)
    assert abs(share - 100 * want / 819e9 / (note["kernels_ms_a_run"] / 1e3)
               ) < 1e-6
    assert 50 < share < 100
    for broken in ({"trace": None}, {"peaks": None}):
        assert read({**_hand_run(), **broken}, params) is None
    parent = _hand_run()
    del parent["counters"]["decode_full_positions_total"]
    assert read(parent, params) is None         # no counter: silent


def test_prefill_roofline_counts_whole_runs_by_layer_kind():
    """Each whole prefill run paired with the request it served, counted
    over that prompt's real columns, not the bucket's padding."""
    read, params = _reader("window_prefill_roofline")
    run = _hand_run()
    share = read(run, params)
    d = _dims()
    note = run["notes"]["window_prefill_roofline"]
    assert [(r["bucket"], r["prompt_len"]) for r in note["runs"]] == [
        (8192, 5000), (8192, 7000)]
    assert note["unpaired"] == 0 and note["device_clock_shift_ns"] > 0
    assert all(abs(r["after_admit_ms"] - 2.0) < 0.2 for r in note["runs"])

    def least(n):
        fw, bw = flops_afmoe.prefill_attention_cost(d, n, True)
        ff, bf = flops_afmoe.prefill_attention_cost(d, n, False)
        return 12 * max(fw / 197e12, bw / 819e9) + 4 * max(ff / 197e12,
                                                            bf / 819e9)

    spent = 2 * (12 * 1.3e-3 + 4 * 3.5e-3)
    assert abs(share - 100 * (least(5000) + least(7000)) / spent) < 1e-6
    # the padded bucket's pairs would read well above it
    assert least(8192) > 1.5 * least(5000)
    assert 0 < share < 100
    silent = _hand_run(prefill_runs=0)
    assert read(silent, params) is None
    assert "silent" in silent["notes"]["window_prefill_roofline"]


@pytest.mark.parametrize("broken", ["no_mark", "no_stamp", "wrong_bucket"])
def test_prefill_roofline_leaves_out_what_it_cannot_pair(broken):
    """No mark or no stamps: silent; a run whose bucket is not its
    request's is left out and counted."""
    read, params = _reader("window_prefill_roofline")
    run = _hand_run()
    if broken == "no_mark":
        run["trace"]["host"] = [e for e in run["trace"]["host"]
                                if e[0] != params["mark"]]
    elif broken == "no_stamp":
        run["clock_mark_s"] = None
    else:
        run["requests"][0]["prompt_len"] = 3000      # a 4,096 bucket
    share = read(run, params)
    note = run["notes"]["window_prefill_roofline"]
    if broken == "wrong_bucket":
        assert share is not None and note["unpaired"] == 1
        assert [r["prompt_len"] for r in note["runs"]] == [7000]
    else:
        assert share is None and "silent" in note


def test_prefill_runs_pair_with_admissions_in_order():
    """The k-th run is the k-th admitted: two admitted at one boundary
    before either ran take the two runs in order; a run that started
    before the first stamp pairs with none."""
    read, _ = _reader("window_prefill_roofline")
    pair_runs, bucket = (read.__globals__[k] for k in ("pair_runs",
                                                       "bucket"))
    admits = [(100, 5000), (200, 6000), (205, 7000), (900, 5500)]
    assert pair_runs([110, 230, 260], admits, 0) == [0, 1, 2]
    assert pair_runs([50, 230, 260], admits, 0) == [None, 1, 2]
    assert pair_runs([210, 260], admits, 0) == [1, 2]
    # within the clocks' slack a run may seem to start before its stamp
    assert pair_runs([98, 198], admits[:2], 5) == [0, 1]
    assert [bucket(n) for n in (3072, 4096, 4097, 8192)] == [
        4096, 4096, 8192, 8192]


def test_mfu_counts_each_layer_s_reach_and_the_experts_held():
    read, params = _reader("window_serve_mfu_pct")
    d = _dims()
    run = _hand_run()
    share = read(run, params)
    a = flops_afmoe.attended(run["stamped"]["spans"], 2048)
    want = flops_afmoe.serve_flops(d, a["tokens"], a["heads"],
                                   14 * 16.0 * 1000,
                                   a["full_positions"],
                                   a["window_positions"])
    assert abs(share - 100 * want / (51.0 * 197e12)) < 1e-9
    assert 0 < share < 100
    silent = _hand_run()
    del silent["counters"]["expert_assignments_held_total"]
    assert read(silent, params) is None


def test_expert_roofline_reads_this_cell_s_expert_size():
    """The accepted reader, handed this cell's dims: 12.58 MB an expert
    (2048 x 1024 x 3 in bf16), so the metric could read this cell (its
    list of cells is held to one by the accepted tests)."""
    read, params = _reader("expert_ffn_roofline")
    assert flops_qwen3_next.expert_bytes(_dims()) == 12582912
    run = _hand_run()
    share = read(run, params)
    assert abs(share - 100 * 140 * 12582912 / 819e9 / 2.8e-3) < 1e-6
