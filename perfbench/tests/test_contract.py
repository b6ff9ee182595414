"""The harness is open, and the benchmark's data holds together: the checks
that need no node and no device (seconds in all). A later PR brings a
configuration's stake, a served cell's arrival schedule and peer delays, a
traffic kind and a reader as files; what each of those files may say, and
what the harness makes of it, is pinned here.
"""

import json
import os
import socket

import pytest

from perfbench.harness import cells, corpus, drive, peers

BENCH = cells.benchmark()
CONFIGS = [c["name"] for c in BENCH["configs"]]
CELLS = [w["name"] for w in BENCH["workloads"]]
SERVED = [n for n in CELLS if cells.Cell(n).traffic["kind"] == "served"]
KIND_FILES = sorted(f[:-3] for f in os.listdir(os.path.join(cells.BENCH, "kinds"))
                    if f.endswith(".py"))


def config_of(name: str) -> dict:
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    with open(os.path.join(cells.ROOT, entry["file"])) as f:
        return json.load(f)


# ------------------------------------------------------------------ stake


@pytest.mark.parametrize("name", CONFIGS)
def test_a_configuration_without_a_stake_list_has_stake_each_for_all(name):
    config = config_of(name)
    assert "stake" not in config  # the three files as they were: no value changed
    assert corpus.powers_of(config) == [config["stake_each"]] * config["validators"]


def test_a_stake_list_is_taken_as_written():
    skewed = {"validators": 7, "stake": [40, 20, 10, 5, 3, 1, 1], "stake_each": 99}
    assert corpus.powers_of(skewed) == [40, 20, 10, 5, 3, 1, 1]
    assert corpus.powers_of(skewed) is not skewed["stake"]  # a copy: the file's list stays


@pytest.mark.parametrize("stake", [[40, 20], [1] * 8, [3, 2, 1, 0, 1, 1, 1], [3, 2, -1, 1, 1, 1, 1],
                                   [3, 2, 1.5, 1, 1, 1, 1], [3, 2, True, 1, 1, 1, 1]])
def test_a_stake_list_that_is_not_one_positive_int_a_validator_is_refused(stake):
    with pytest.raises(ValueError, match="stake"):
        corpus.powers_of({"validators": 7, "stake": stake})


# --------------------------------------------------------------- arrivals


@pytest.mark.parametrize("name", SERVED)
def test_evenly_spaced_is_the_schedule_the_cells_ran_before_it_was_a_file(name):
    traffic = cells.Cell(name).traffic
    assert traffic["arrivals"] == "evenly_spaced"
    rate = float(traffic["rate_tps"])
    seconds = BENCH["run_seconds"]
    served = cells.kind("served")
    got_rate, window_s, offsets, open_ns, judged = served.plan(traffic, seconds, seed=2**31 + 5)
    n_lead, n_win = round(rate * traffic["lead_s"]), round(rate * seconds)
    period_ns = 1e9 / rate
    # to the nanosecond what client.py and the injector each computed themselves
    assert offsets == [int(i * period_ns) for i in range(n_lead + n_win)]
    assert offsets == [int(i * 1e9 / rate) for i in range(n_lead + n_win)]
    assert offsets == served.offsets_of(traffic, len(offsets), rate, seed=1)  # no seed in it
    assert (got_rate, window_s, open_ns) == (rate, n_win / rate, int(n_lead * 1e9 / rate))
    assert judged == list(range(n_lead, n_lead + n_win))  # the indexes judged before


def test_a_schedule_is_held_to_its_length_and_its_order(monkeypatch):
    served = cells.kind("served")
    traffic = {"arrivals": "evenly_spaced", "rate_tps": 10, "lead_s": 1}
    for bad in ([0, 5, 4, 6], [0, 1, 2], [-1, 0, 1, 2]):
        monkeypatch.setattr(cells, "arrivals", lambda name, bad=bad: lambda *a: bad)
        with pytest.raises(ValueError, match="arrivals 'evenly_spaced'"):
            served.offsets_of(traffic, 4, 10.0, seed=0)
    # a schedule may bunch its txs as it likes: judged are those due in the window
    bursts = [0, 0, 10**9, 10**9, 10**9, 2 * 10**9 - 1, 2 * 10**9, 3 * 10**9] + [4 * 10**9] * 22
    monkeypatch.setattr(cells, "arrivals", lambda name: lambda *a: bursts)
    _, window_s, offsets, open_ns, judged = served.plan(traffic, 2, seed=0)
    assert (window_s, open_ns, judged) == (2.0, 10**9, [2, 3, 4, 5, 6])


def test_the_client_takes_its_job_from_stdin_whatever_its_size():
    """480 tx/s over 42 s is past what one argument holds (128 KiB). The
    job reaches the client whole: it gets as far as the socket, where a
    listener that hangs up at once ends it."""
    served = cells.kind("served")
    offsets = [int(i * 1e9 / 480) for i in range(480 * 42)]
    with socket.socket() as listener:
        listener.bind(("127.0.0.1", 0))
        listener.listen(1)
        job = {"host": "127.0.0.1", "port": listener.getsockname()[1], "t0_ns": 0,
               "offsets_ns": offsets, "first_tx": 0, "tx_bytes": 64, "tag": "t", "senders": 1,
               "wait_s": 0.1}
        assert len(json.dumps(job)) > 128 * 1024
        proc = served.start_client(job)
        try:
            conn, _ = listener.accept()
            conn.close()
            proc.communicate(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    assert proc.returncode != 0  # the hang-up, not a job it could not read
    with pytest.raises(RuntimeError, match="client failed"):
        served.collect_client(proc, timeout=1)


# ------------------------------------------------------------ peer delays


def test_one_delay_is_one_frame_of_every_peer_in_validator_order():
    signers = list(range(1, 64))
    for delay in (0, 12.5):
        delays = peers.delays_of({"peer_delay_ms": delay}, 64)
        assert delays == [float(delay)] * 64
        assert peers.frames(delays, signers) == [(float(delay), list(enumerate(signers)))]


def test_k_delays_are_k_frames_by_rising_delay_with_the_signers_order_kept():
    delays = peers.delays_of({"peer_delay_ms": [0, 40, 15, 15, 40, 0.5, 40]}, 7)
    groups = peers.frames(delays, [1, 2, 3, 4, 5, 6])
    # (place in the corpus, validator): the corpus holds the signers' signatures by place
    assert groups == [(0.5, [(4, 5)]), (15.0, [(1, 2), (2, 3)]), (40.0, [(0, 1), (3, 4), (5, 6)])]
    # the node's own validator has an entry that no frame uses
    assert all(v != 0 for _, group in groups for _, v in group)
    # every delivery of every tx, merged into one schedule in the order it falls due
    merged = peers.schedule([0, 10_000_000, 20_000_000], groups)
    assert merged == sorted(merged) and len(merged) == 9
    assert merged[:4] == [(500_000, 0, 0), (10_500_000, 1, 0), (15_000_000, 0, 1),
                          (20_500_000, 2, 0)]
    assert peers.schedule([0, 7], [(0.0, [(0, 1)])]) == [(0, 0, 0), (7, 1, 0)]


@pytest.mark.parametrize("delay", [[0, 1], [0] * 8, [0, 0, -1, 0, 0, 0, 0], "0", None,
                                   [0, 0, "1", 0, 0, 0, 0], [0, 0, True, 0, 0, 0, 0]])
def test_peer_delays_that_are_not_one_number_or_one_a_validator_are_refused(delay):
    with pytest.raises((ValueError, TypeError)):
        peers.delays_of({"peer_delay_ms": delay}, 7)


@pytest.mark.parametrize("powers, delays, own, at", [
    ([10] * 4, [7.0] * 4, 0, 7.0),  # one number: that number
    ([10] * 64, [0.0] * 64, 0, 0.0),
    ([40, 20, 10, 5, 3, 1, 1], [0, 30, 10, 10, 20, 20, 20], 0, 10.0),  # 40 + 10 + 5 = 55 of 80
    ([40, 20, 10, 5, 3, 1, 1], [0, 0, 10, 10, 20, 20, 20], 0, 0.0),  # 40 + 20
    ([40, 20, 10, 5, 3, 1, 1], [9, 5, 10, 10, 20, 20, 30], 1, 9.0),  # own 20, then validator 0
    ([1, 1, 1, 1], [5, 1, 2, 3], None, 3.0),  # the node signs nothing: three of four
    ([70, 10, 10, 10], [5, 5, 5, 5], 0, 0.0),  # the node's own vote decides
])
def test_the_quorum_delay_is_where_the_delivered_stake_first_passes_two_thirds(
        powers, delays, own, at):
    assert peers.quorum_delay_ms(powers, [float(d) for d in delays], own) == at


# --------------------------------------------- kinds and readers, by file


@pytest.mark.parametrize("finder, name, has", [
    (cells.kind, "flood", "run"), (cells.kind, "served", "run"),
    (cells.arrivals, "evenly_spaced", "__call__"),
    (cells.metric_reader, "ingest_us_per_vote.val64flood", "__call__"),
])
def test_a_kind_a_schedule_and_a_reader_are_found_by_file(finder, name, has):
    assert callable(getattr(finder(name), has))


@pytest.mark.parametrize("finder, name, path", [
    (cells.kind, "ticker_flood", "perfbench/kinds/ticker_flood.py"),
    (cells.arrivals, "burst", "perfbench/arrivals/burst.py"),
    (cells.metric_reader, "recover_s.val4", "perfbench/metrics/recover_s.py"),
])
def test_a_name_without_its_file_says_which_file_is_missing(finder, name, path):
    with pytest.raises(FileNotFoundError, match=path.replace(".", r"\.")):
        finder(name)


def test_a_cell_of_a_kind_without_a_file_does_not_run(tmp_path):
    cell = cells.Cell(CELLS[0])
    cell.traffic = dict(cell.traffic, kind="ticker_flood")
    with pytest.raises(FileNotFoundError, match="ticker_flood"):
        drive.run_cell(cell, drive.Options(seed=1, seconds=1, scalar=True, scratch=str(tmp_path)))


def test_every_kind_file_states_what_it_runs_and_every_traffic_file_names_a_kind():
    used = set()
    for f in os.listdir(os.path.join(cells.BENCH, "traffic")):
        with open(os.path.join(cells.BENCH, "traffic", f)) as fh:
            traffic = json.load(fh)
        used.add(traffic["kind"])
        if "arrivals" in traffic:
            assert callable(cells.arrivals(traffic["arrivals"]))
    assert used <= set(KIND_FILES)
    for name in KIND_FILES:
        kind = cells.kind(name)
        assert callable(kind.run) and set(kind.RUNS) == {"hosted_nodes", "consensus_ticker", "app"}


@pytest.mark.parametrize("kind", KIND_FILES)
@pytest.mark.parametrize("key, stated", [
    ("hosted_nodes", 2), ("hosted_nodes", 4), ("consensus_ticker", True), ("app", "counter"),
    ("app", None), ("hosted_nodes", True), ("consensus_ticker", 0),
])
def test_a_kind_refuses_a_configuration_that_states_what_it_does_not_run(kind, key, stated):
    runs = cells.kind(kind).RUNS
    config = dict(config_of(CONFIGS[0]), **{key: stated})
    with pytest.raises(ValueError, match=key):
        drive.check_runs(kind, config, runs)
    config.pop(key)  # a file that does not say is not run either
    with pytest.raises(ValueError, match=key):
        drive.check_runs(kind, config, runs)


@pytest.mark.parametrize("name", CELLS)
def test_the_refusal_comes_before_any_set_up(name, monkeypatch, tmp_path):
    cell = cells.Cell(name)
    cell.config = dict(cell.config, hosted_nodes=2)
    monkeypatch.setattr(drive, "set_up", lambda *a, **kw: pytest.fail("set-up was reached"))
    monkeypatch.setattr(drive, "device_info", lambda *a, **kw: pytest.fail("JAX was reached"))
    with pytest.raises(ValueError, match="hosted_nodes = 2"):
        drive.run_cell(cell, drive.Options(seed=1, seconds=1, scratch=str(tmp_path)))


# --------------------------------------------------------------- counters


def counters_ctx(opened, closed):
    return {"counters": {"open": {"pipeline": {}, "ingest": opened},
                         "close": {"pipeline": {}, "ingest": closed}}}


def test_ingest_us_per_vote_is_the_ingests_thread_cpu_over_its_votes_in_the_window():
    read = cells.metric_reader("ingest_us_per_vote.flood")
    at_open = {"votes": 1_000_000, "cpu_s": 6.5, "fast": 1_000_000, "general": 0, "primed": 0}
    at_close = {"votes": 2_280_768, "cpu_s": 14.5048, "fast": 2_280_768, "general": 0, "primed": 0}
    assert read(counters_ctx(at_open, at_close)) == pytest.approx(6.25)
    assert read(counters_ctx(at_open, at_open)) is None  # no vote came in: nothing, never 0
    assert read(counters_ctx(at_close, at_open)) is None


def test_pipeline_delta_is_what_the_readers_of_ctx_pipeline_always_got():
    at_open = {"steps": 10, "prep_s": 1.0, "route_s": 0.5, "dispatch_wait_s": 0.25, "late_votes": 3,
               "coalesce": {"full_batches": 9, "linger_flushes": 1, "cold_fallback_votes": 0}}
    at_close = {"steps": 210, "prep_s": 31.0, "route_s": 6.5, "dispatch_wait_s": 0.75,
                "late_votes": 3,
                "coalesce": {"full_batches": 209, "linger_flushes": 1, "cold_fallback_votes": 0}}
    assert drive.pipeline_delta(at_close, at_open, ("full_batches", "linger_flushes")) == {
        "steps": 200, "prep_s": 30.0, "route_s": 6.0, "dispatch_wait_s": 0.5,
        "full_batches": 200, "linger_flushes": 0,
    }
    assert "full_batches" not in drive.pipeline_delta(at_close, at_open, ("linger_flushes",))


# ------------------------------------------------- the lists, one another


def test_every_layer_of_benchmark_json_is_a_layer_of_perf_md():
    path = os.path.join(cells.ROOT, "PERF.md")
    if not os.path.isfile(path):
        pytest.skip("a checkout of the benchmark alone has no PERF.md")
    with open(path) as f:
        perf = f.read()
    for layer in sorted({m["layer"] for m in BENCH["per_layer"]}):
        assert f"| {layer} " in perf, layer  # a row of section 3's table, letter for letter


def test_every_cell_reports_a_metric_of_every_layer_its_kind_drives():
    """The four ingest entries are this PR's: every cell delivers votes at the
    vote pool, so every cell reports the ingest's cost; and each cell keeps at
    least one metric that reads the device's trace and one that reads a counter."""
    for name in CELLS:
        per_layer = cells.Cell(name).per_layer
        assert [m for m in per_layer if m["layer"] == "vote pool ingest"], name
        assert {m["source"] for m in per_layer} >= {"device_trace", "program_counter"}, name
