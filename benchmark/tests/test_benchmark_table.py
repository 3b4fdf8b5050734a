"""``BENCHMARK.json``'s per-layer table (PR 49): every reader is declared
ONCE, under its file's name, with its cells in ``workloads`` — no copy
under a suffix (`.seqrec` / `.lfm2` / `.st`) can come back unnoticed, a
cell cannot gain or lose a reader unnoticed, and ``harness.Cell``
refuses a table that breaks the rule
(``JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_benchmark_table.py
-q``)."""

import copy
import json
import os
import time

import pytest

import harness

with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
TABLE = BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]

#: what every train verb reports
VERB = {"read_training_s", "programs_compiled", "device_idle_pct",
        "hbm_peak_GB", "read_scan_s", "save_s", "host_untraced_s"}
ALS = VERB | {
    "als_prepare_s", "als_device_s", "als_step_mfu_pct", "gather_gram_ms",
    "gather_gram_roofline", "layout_in_train_s", "h2d_s", "checkpoint_s",
    "fetch_s", "gram_real_rows_pct", "chol_solve_ms", "gram_dma_real_pct",
    "layout_order_s", "layout_fill_s", "gram_waits_per_copy_pct",
    "gram_resident_rows_pct", "snapshot_load_s"}
#: what every ``sequentialrec`` backbone reports
SEQ = VERB | {
    "save_write_s", "save_sync_s", "seqrec_device_s", "seqrec_step_mfu_pct",
    "moe_route_dispatch_ms", "moe_experts_ms", "moe_experts_roofline",
    "seqrec_head_loss_ms", "seqrec_optimizer_ms", "moe_load_max_over_mean",
    "seq_pack_real_pct", "seqrec_pack_s", "seqrec_unscoped_ms",
    "seqrec_stack_ms", "seqrec_cast_ms", "seqrec_norm_residual_ms",
    "moe_ragged_dot_ms"}
GQA = {"gqa_attention_ms", "gqa_attention_roofline", "gqa_proj_ms",
       "attn_tile_real_pct"}
EXPECTED = {
    "als-ml20m-train": ALS,
    "ials-lastfm360k-train": ALS,
    "seqrec-glm47flash-train": SEQ | {
        "mla_attention_ms", "mla_attention_roofline", "mla_proj_ms",
        "seqrec_ffn_ms", "attn_tile_real_pct"},
    "seqrec-lfm2-8b-a1b-train": SEQ | GQA | {
        "seqrec_ffn_ms", "shortconv_ms", "shortconv_roofline",
        "shortconv_proj_ms"},
    "seqrec-smallthinker-21b-train": SEQ | GQA | {
        "swa_attention_ms", "swa_attention_roofline", "swa_proj_ms",
        "swa_window_pairs_pct", "swa_tile_real_pct"},
    "seqrec-sdar-30b-a3b-train": SEQ | {
        "bd_attention_ms", "bd_attention_roofline", "bd_proj_ms",
        "bd_noise_ms", "bd_tile_real_pct", "bd_masked_pct"},
    "seqrec-qwen3next-80b-a3b-train": SEQ | GQA | {
        "seqrec_ffn_ms", "gdn_scan_ms", "gdn_scan_roofline", "gdn_conv_ms",
        "gdn_proj_ms", "gdn_boundary_chunks_pct"},
}


def test_the_table_names_every_reader_once():
    names = [m["name"] for m in TABLE]
    assert not [n for n in names if "." in n]
    assert len(names) == len(set(names)) == 68 <= 128
    assert set(names) == set().union(*EXPECTED.values())
    assert sorted(EXPECTED) == sorted(CELLS) and len(CELLS) == 7


@pytest.mark.parametrize("spec", TABLE, ids=lambda m: m["name"])
def test_an_entry_has_its_cells_its_reader_and_a_metric_to_move(spec):
    assert set(spec) == {"name", "unit", "better", "source", "layer",
                         "moves", "workloads"}
    assert spec["workloads"] and set(spec["workloads"]) <= set(CELLS)
    # in the order of BENCHMARK.json's workloads
    assert spec["workloads"] == [c for c in CELLS if c in spec["workloads"]]
    read = harness.load_module("layers", spec["name"]).read
    # a reader that finds nothing to read returns nothing, never 0
    assert callable(read) and read({}) is None
    for cell in spec["workloads"]:
        reported = {m["name"] for m in BENCH["end_to_end"]
                    if harness.metric_applies(m, cell)}
        assert spec["moves"] in reported


@pytest.mark.parametrize("name", CELLS)
def test_a_cell_runs_exactly_its_readers(name):
    cell = harness.Cell(name, 1, 1.0, True, True, time.perf_counter())
    assert {m["name"] for m in cell.per_layer} == EXPECTED[name]
    assert len(cell.per_layer) == len(EXPECTED[name])


def _broken(kind: str) -> dict:
    bench = copy.deepcopy(BENCH)
    first = bench["per_layer"][0]
    if kind == "dotted":
        bench["per_layer"].append(dict(first, name=first["name"] + ".lfm2"))
    elif kind == "repeated":
        bench["per_layer"].append(dict(first))
    else:
        first["workloads"] = first["workloads"] + ["seqrec-no-such-train"]
    return bench


@pytest.mark.parametrize("kind, says", [("dotted", "no dot"),
                                        ("repeated", "twice"),
                                        ("orphaned", "no such workload")])
def test_cell_refuses_a_dotted_a_repeated_and_an_orphaned_entry(
        kind, says, tmp_path, monkeypatch):
    """On a temporary copy of the checkout's two places ``Cell`` reads:
    ``BENCHMARK.json`` at the root (the configuration's ``file`` is read
    from the same root) — the benchmark's own files stay where they
    are."""
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(_broken(kind), f)
    os.symlink(harness.BENCH, tmp_path / "benchmark")
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    with pytest.raises(harness.BenchFailure, match=says):
        harness.Cell(CELLS[0], 1, 1.0, False, True, time.perf_counter())
    # and the table as committed passes from the same copy
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(BENCH, f)
    assert harness.Cell(CELLS[0], 1, 1.0, False, True,
                        time.perf_counter()).per_layer


def test_als_step_mfu_is_the_mathematics_over_busy_seconds_and_peak():
    """PR 49's one new reader: ``roofline.train_flops`` of the traced
    train (the generator leaves it in ``obs``) over the device's busy
    seconds × the bf16 peak; nothing where there is no trace, no busy
    second, no count or no peak."""
    import roofline
    import trace_reduce

    read = harness.load_module("layers", "als_step_mfu_pct").read
    flops = roofline.train_flops(20_000_263, 138_493, 26_744, 64, 10)
    assert flops == pytest.approx(3.486e12, rel=1e-3)
    obs = {"trace": trace_reduce.TraceSummary(9.0, 3.5, 1),
           "als_train_flops": flops,
           "peaks": {"bf16_flops_per_s": 197e12}}
    assert read(obs) == pytest.approx(100 * flops / (3.5 * 197e12))
    assert 0.0 < read(obs) < 5.0
    for without in obs:
        assert read({k: v for k, v in obs.items() if k != without}) is None
    idle = dict(obs, trace=trace_reduce.TraceSummary(9.0, 0.0, 0))
    assert read(idle) is None


def test_the_readme_lists_every_reader_with_its_layer_and_source():
    """``benchmark/README.md``'s table fell behind the layer files from
    PR 25 to PR 45; a row per entry, or this fails."""
    with open(os.path.join(harness.BENCH, "README.md")) as f:
        rows = {ln.split("|")[1].strip(): ln for ln in f
                if ln.startswith("| `") and ln.count("|") == 7}
    for spec in TABLE:
        row = rows[f"`{spec['name']}`"]
        assert [c.strip() for c in row.split("|")[2:6]] == [
            spec["layer"], spec["unit"], spec["better"], spec["source"]]
    assert len(rows) == len(TABLE)
