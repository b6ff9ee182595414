"""The readers of the span metrics (PR 28), each on a hand-made ``ctx``:
the arithmetic its docstring states, and None where its family is empty,
which is what a program that does not record the family gives."""

import pytest

from perfbench.harness import cells

T_OPEN, T_CLOSE = 100.0, 135.0


def ctx_of(families: dict, *, votes=0, lat_ms=None, quorum_delay_ms=0.0):
    """``families``: family -> durations (s) of the spans that began in
    the window, as ``SystemUnderTest.spans`` hands them to a reader."""

    def spans(family, t0, t1):
        assert (t0, t1) == (T_OPEN, T_CLOSE)
        return list(families.get(family, []))

    return {
        "spans": spans, "t_open": T_OPEN, "t_close": T_CLOSE, "window_s": T_CLOSE - T_OPEN,
        "votes": votes, "client": None if lat_ms is None else {"lat_ms": lat_ms, "late_ms": []},
        "trace": None, "quorum_delay_ms": quorum_delay_ms,
    }


MEDIANS = {
    "rpc_ms": "rpc_ingest", "sign_wait_ms": "sign_wait", "prep_ms": "host_prep",
    "dispatch_ms": "dispatch", "device_busy_ms": "device_busy",
    "collect_wait_ms": "collect_wait", "route_ms": "route",
    "commit_apply_ms": "commit_apply", "publish_ms": "publish", "pickup_ms": "pickup_wait",
    "vote_wait_ms": "vote_wait",
}


@pytest.mark.parametrize("stem", sorted(MEDIANS))
def test_median_readers(stem):
    read = cells.metric_reader(stem + ".served")
    family = MEDIANS[stem]
    # the median, not the mean: one stalled step does not move it
    assert read(ctx_of({family: [0.001, 0.002, 0.250]})) == pytest.approx(2.0)
    assert read(ctx_of({family: [0.004, 0.002]})) == pytest.approx(3.0)
    assert read(ctx_of({})) is None
    assert read(ctx_of({"some_other_family": [1.0]})) is None


def test_route_tally_per_vote():
    family = "route_tally"
    read = cells.metric_reader("route_tally_us_per_vote.flood")
    ctx = ctx_of({family: [0.050, 0.070, 0.060]}, votes=3 * 4096)
    assert read(ctx) == pytest.approx(1e6 * 0.180 / 12288)
    assert read(ctx_of({}, votes=4096)) is None
    assert read(ctx_of({family: [0.05]}, votes=0)) is None


def test_gc_share_is_the_collections_share_of_the_window():
    read = cells.metric_reader("gc_share.flood")
    ctx = ctx_of({"gc_pause": [0.25] * 14 + [0.0005] * 2000})  # 3.5 s full + 1 s young of 35
    assert read(ctx) == pytest.approx(100.0 * 4.5 / 35.0)
    assert read(ctx_of({})) is None


WATERFALL_MS = {
    "vote_wait": 4.9, "host_prep": 0.5, "dispatch": 0.6, "collect_wait": 2.0,
    "route_tally": 0.4, "commit_apply": 0.3, "publish": 0.8,
}


def test_unattributed_reads_nought_on_a_waterfall_that_adds_up():
    read = cells.metric_reader("unattributed_ms.served")
    families = {name: [ms / 1e3] * 5 for name, ms in WATERFALL_MS.items()}
    total = sum(WATERFALL_MS.values())
    assert read(ctx_of(families, lat_ms=[total] * 5)) == pytest.approx(0.0, abs=1e-9)
    # what no span covers is what is left: the client's median less the medians
    assert read(ctx_of(families, lat_ms=[total + 5.4] * 4 + [60_000.0])) == pytest.approx(5.4)
    # the node's own vote arrives inside the hold, and route's tail runs beside
    # the committer: neither is on the path, neither is subtracted
    # (and the step's pickup and hold are what vote_wait holds, per tx)
    for off_path in ("rpc_ingest", "sign_wait", "sign_walk", "route", "pickup_wait", "linger_bulk"):
        families[off_path] = [0.0009] * 5
    assert read(ctx_of(families, lat_ms=[total] * 5)) == pytest.approx(0.0, abs=1e-9)
    # the votes that complete the quorum start the path: a cell that delays the
    # peers says at which delay the delivered stake passes 2/3 (harness/peers.py)
    assert read(ctx_of(families, lat_ms=[total + 50.0] * 5, quorum_delay_ms=50.0)) == pytest.approx(
        0.0, abs=1e-9)


@pytest.mark.parametrize("missing", sorted(WATERFALL_MS))
def test_unattributed_is_none_where_a_family_is_empty(missing):
    read = cells.metric_reader("unattributed_ms.val64")
    families = {name: [ms / 1e3] for name, ms in WATERFALL_MS.items() if name != missing}
    assert read(ctx_of(families, lat_ms=[9.5])) is None
    assert read(ctx_of(dict(families, **{missing: [0.001]}), lat_ms=[])) is None
    assert read(ctx_of(dict(families, **{missing: [0.001]}))) is None  # no client: a flood


def test_every_new_metric_names_its_reader_its_cell_and_what_it_moves():
    """Read from ``BENCHMARK.json``: a suffix names one list of cells and one
    end-to-end metric, which each of those cells reports; every span metric
    has a reader that finds nothing among no spans; its layer is one that
    some other source's metric, or PERF.md's table, already names."""
    bench = cells.benchmark()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    all_cells = [w["name"] for w in bench["workloads"]]
    by_suffix = {}
    for m in bench["per_layer"]:
        assert "." in m["name"], m["name"]  # quantity.cells
        by_suffix.setdefault(m["name"].split(".", 1)[1], []).append(m)
    assert len(by_suffix) == len({tuple(ms[0]["workloads"]) for ms in by_suffix.values()})
    for suffix, ms in by_suffix.items():
        assert len({(tuple(m["workloads"]), m["moves"]) for m in ms}) == 1, suffix
        moved = e2e[ms[0]["moves"]]
        assert set(ms[0]["workloads"]) <= set(moved.get("workloads", all_cells)), suffix
        stems = [cells.stem(m["name"]) for m in ms]
        assert len(set(stems)) == len(stems), suffix  # one reading of a quantity in a cell
    spans = [m for m in bench["per_layer"] if m["source"] == "program_span"]
    assert spans and len(spans) == sum(
        1 for ms in by_suffix.values() for m in ms if m["source"] == "program_span")
    for m in spans:
        assert cells.metric_reader(m["name"])(ctx_of({}, lat_ms=[10.0])) is None, m["name"]
    by_stem = {}
    for m in bench["per_layer"]:
        by_stem.setdefault(cells.stem(m["name"]), set()).add(
            (m["unit"], m["better"], m["source"], m["layer"]))
    assert all(len(v) == 1 for v in by_stem.values()), by_stem  # one quantity, one arithmetic
    # the device's idle share is the profiler's alone: no reading of it from host timestamps
    assert not [m for m in bench["per_layer"] if m["layer"] == "device"
                and m["source"] != "device_trace"]
