"""txlint gate + per-pass fixture tests.

test_tree_is_clean is the tier-1 wiring of ``tools/lint.py --check``: the
committed tree must carry zero unsuppressed violations (and zero parse
errors). The fixture tests prove each pass actually FIRES on a minimal
reproduction, so a refactor that silently lobotomizes a pass fails here
rather than letting the tree gate rot into a no-op.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

from txflow_tpu.analysis.core import lint_source, lint_tree
from txflow_tpu.analysis.twins import TwinPathPass, update_pins

REPO_ROOT = Path(__file__).resolve().parent.parent


def _src(text: str) -> str:
    return textwrap.dedent(text)


def _rules(violations) -> list[str]:
    return [v.rule for v in violations]


# ---------------------------------------------------------------------------
# the tree gate (tier-1)
# ---------------------------------------------------------------------------


def test_tree_is_clean():
    report = lint_tree(REPO_ROOT)
    assert report["errors"] == []
    msgs = "\n".join(v.format() for v in report["violations"])
    assert not report["violations"], f"unsuppressed txlint violations:\n{msgs}"
    # every suppression in the tree documents itself
    for v in report["suppressed"]:
        assert v.justification, v.format()


def test_cli_check_and_json():
    out = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "lint.py"), "--check"],
        capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    out = subprocess.run(
        [sys.executable, str(REPO_ROOT / "tools" / "lint.py"), "--json"],
        capture_output=True, text=True,
    )
    assert out.returncode == 0
    report = json.loads(out.stdout)
    assert report["violations"] == []
    assert report["files_scanned"] > 50
    assert isinstance(report["suppressed_counts"], dict)


# ---------------------------------------------------------------------------
# lock-blocking
# ---------------------------------------------------------------------------


def test_lock_blocking_direct():
    active, _ = lint_source(_src("""
        class C:
            def send(self, frame):
                with self._mtx:
                    self.sock.sendall(frame)
    """), "txflow_tpu/x.py")
    assert _rules(active) == ["lock-blocking"]
    assert "sendall" in active[0].message
    assert "_mtx" in active[0].message


def test_lock_blocking_taint_through_self_call():
    active, _ = lint_source(_src("""
        class C:
            def _flush(self):
                self.wal.write(b"x")

            def ingest(self, tx):
                with self._mtx:
                    self._flush()
    """), "txflow_tpu/x.py")
    assert _rules(active) == ["lock-blocking"]
    assert "reaches blocking" in active[0].message


def test_lock_blocking_outside_lock_is_fine():
    active, _ = lint_source(_src("""
        class C:
            def send(self, frame):
                self.sock.sendall(frame)
                with self._mtx:
                    self.n += 1
    """), "txflow_tpu/x.py")
    assert active == []


def test_lock_blocking_cond_wait_on_held_lock_allowed():
    active, _ = lint_source(_src("""
        class C:
            def pop(self):
                with self._cond:
                    self._cond.wait()
                with self._mtx:
                    self._other.wait()
    """), "txflow_tpu/x.py")
    # waiting on the condition you hold releases it (sanctioned); waiting
    # on anything else while holding a lock is the classic stall
    assert _rules(active) == ["lock-blocking"]
    assert "_other" in active[0].message


def test_suppression_honored_and_recorded():
    active, suppressed = lint_source(_src("""
        class C:
            def send(self, frame):
                with self._wlock:
                    self.sock.sendall(frame)  # txlint: allow(lock-blocking) -- wlock exists to serialize frame writes
    """), "txflow_tpu/x.py")
    assert active == []
    assert _rules(suppressed) == ["lock-blocking"]
    assert suppressed[0].justification.startswith("wlock exists")


def test_suppressed_seed_does_not_taint_callers():
    active, suppressed = lint_source(_src("""
        class C:
            def _flush(self):
                self.wal.write(b"x")  # txlint: allow(lock-blocking) -- append order must match insert order

            def ingest(self, tx):
                with self._mtx:
                    self._flush()
    """), "txflow_tpu/x.py")
    # sanctioning the seed sanctions the chain that reaches it
    assert active == []


def test_bad_suppression_missing_justification():
    active, _ = lint_source(_src("""
        class C:
            def send(self, frame):
                with self._mtx:
                    self.sock.sendall(frame)  # txlint: allow(lock-blocking)
    """), "txflow_tpu/x.py")
    # the allow() without a justification is itself flagged AND does not
    # suppress the underlying violation
    assert sorted(_rules(active)) == ["bad-suppression", "lock-blocking"]


def test_bad_suppression_unknown_rule():
    active, _ = lint_source(_src("""
        x = 1  # txlint: allow(made-up-rule) -- because
    """), "txflow_tpu/x.py")
    assert _rules(active) == ["bad-suppression"]
    assert "made-up-rule" in active[0].message


# ---------------------------------------------------------------------------
# nondeterminism
# ---------------------------------------------------------------------------

_CLOCK_SRC = _src("""
    import time

    def stamp():
        return time.time_ns()
""")


def test_nondeterminism_wall_clock_in_consensus_scope():
    active, _ = lint_source(_CLOCK_SRC, "txflow_tpu/consensus/state.py")
    assert _rules(active) == ["nondeterminism"]
    assert "utils.clock" in active[0].message


def test_nondeterminism_out_of_scope_is_fine():
    active, _ = lint_source(_CLOCK_SRC, "txflow_tpu/p2p/switch.py")
    assert active == []


def test_nondeterminism_clock_seam_allowed():
    active, _ = lint_source(_src("""
        from ..utils.clock import now_ns

        def stamp():
            return now_ns()
    """), "txflow_tpu/consensus/state.py")
    assert active == []


def test_nondeterminism_unseeded_rng_and_set_iteration():
    active, _ = lint_source(_src("""
        import random

        def pick(peers):
            r = random.Random(42)          # seeded: fine
            random.shuffle(peers)          # process-global rng: flagged
            for p in set(peers):           # set order: flagged
                pass
    """), "txflow_tpu/consensus/reactor.py")
    assert sorted(_rules(active)) == ["nondeterminism", "nondeterminism"]


# ---------------------------------------------------------------------------
# thread-join
# ---------------------------------------------------------------------------


def test_thread_join_leaked_thread():
    active, _ = lint_source(_src("""
        import threading

        class Worker:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()
    """), "txflow_tpu/x.py")
    assert _rules(active) == ["thread-join"]


def test_thread_join_daemon_or_joined_ok():
    active, _ = lint_source(_src("""
        import threading

        class A:
            def start(self):
                self._t = threading.Thread(target=self._run, daemon=True)
                self._t.start()

        class B:
            def start(self):
                self._t = threading.Thread(target=self._run)
                self._t.start()

            def stop(self):
                self._t.join()
    """), "txflow_tpu/x.py")
    assert active == []


# ---------------------------------------------------------------------------
# hotpath-sync
# ---------------------------------------------------------------------------

_HOT_SRC = _src("""
    class TxFlow:
        def _collect(self, prep, ticket):
            n = ticket.count.item()
            return n

        def stats(self):
            return self.total.item()
""")


def test_hotpath_sync_in_engine_hot_func():
    active, _ = lint_source(_HOT_SRC, "txflow_tpu/engine/txflow.py")
    # layered: .item() in _collect (hot func) is hotpath-sync; the same
    # call in stats() (cold func, hot MODULE) is host-sync — each site
    # reported exactly once
    assert sorted(_rules(active)) == ["host-sync", "hotpath-sync"]
    hot = next(v for v in active if v.rule == "hotpath-sync")
    cold = next(v for v in active if v.rule == "host-sync")
    assert "_collect" in hot.message
    assert hot.line != cold.line


def test_hotpath_sync_other_modules_exempt():
    # no enumerated hot funcs in verifier.py -> no hotpath-sync; the
    # module-wide host-sync pass still covers every function there
    active, _ = lint_source(_HOT_SRC, "txflow_tpu/verifier.py")
    assert _rules(active) == ["host-sync", "host-sync"]
    active, _ = lint_source(_HOT_SRC, "txflow_tpu/node/node.py")
    assert active == []  # cold module: neither pass applies


# ---------------------------------------------------------------------------
# unlocked-lru
# ---------------------------------------------------------------------------


def test_unlocked_lru_direct_construction_flagged():
    active, _ = lint_source(_src("""
        from ..utils.cache import UnlockedLRUCache

        class Pool:
            def __init__(self):
                self.cache = UnlockedLRUCache(100)
    """), "txflow_tpu/pool/x.py")
    assert _rules(active) == ["unlocked-lru"]
    assert "make_lru" in active[0].message


def test_unlocked_lru_factory_module_exempt():
    active, _ = lint_source(
        "c = UnlockedLRUCache(10)\n", "txflow_tpu/utils/cache.py"
    )
    assert active == []


# ---------------------------------------------------------------------------
# trace-clock
# ---------------------------------------------------------------------------

_RAW_CLOCK_SRC = _src("""
    import time

    def stamp():
        return time.monotonic()
""")


def test_trace_clock_raw_clock_in_traced_module():
    active, _ = lint_source(_RAW_CLOCK_SRC, "txflow_tpu/pool/mempool.py")
    assert _rules(active) == ["trace-clock"]
    assert "utils.clock" in active[0].message


def test_trace_clock_reference_not_just_call():
    # passing the function as a callback smuggles the raw clock too
    active, _ = lint_source(_src("""
        import time

        class C:
            def __init__(self):
                self._clock = time.perf_counter
    """), "txflow_tpu/engine/txflow.py")
    assert _rules(active) == ["trace-clock"]


def test_trace_clock_from_import_flagged():
    active, _ = lint_source(
        "from time import monotonic\n", "txflow_tpu/reactors/x.py"
    )
    assert _rules(active) == ["trace-clock"]


def test_trace_clock_seam_and_sleep_allowed():
    active, _ = lint_source(_src("""
        import time

        from ..utils.clock import monotonic

        def pace():
            t0 = monotonic()
            time.sleep(0.01)
            return monotonic() - t0
    """), "txflow_tpu/trace/tracer.py")
    assert active == []


def test_trace_clock_out_of_scope_exempt():
    # engine/ is scoped to the ONE traced file; execution.py keeps its
    # untraced perf_counter accounting, and p2p is outside the scope
    for path in ("txflow_tpu/engine/execution.py", "txflow_tpu/p2p/switch.py"):
        active, _ = lint_source(_RAW_CLOCK_SRC, path)
        assert active == [], path


def test_trace_clock_suppression_honored():
    active, suppressed = lint_source(_src("""
        import time

        def stamp():
            return time.time()  # txlint: allow(trace-clock) -- wall stamp for log line only
    """), "txflow_tpu/admission/controller.py")
    assert active == []
    assert _rules(suppressed) == ["trace-clock"]


# ---------------------------------------------------------------------------
# twin-path
# ---------------------------------------------------------------------------


def _twin_repo(tmp_path: Path) -> tuple[Path, Path]:
    root = tmp_path / "repo"
    (root / "pkg").mkdir(parents=True)
    (root / "tests").mkdir()
    (root / "pkg" / "pool.py").write_text(_src("""
        class Pool:
            def check_tx(self, tx):
                return tx * 1

            def check_tx_many(self, txs):
                return [t * 1 for t in txs]
    """))
    (root / "tests" / "test_parity.py").write_text("def test_parity(): pass\n")
    pin_file = tmp_path / "twins.json"
    pin_file.write_text(json.dumps({
        "twins": {
            "pool-ingest": {
                "functions": {
                    "pkg/pool.py::Pool.check_tx": None,
                    "pkg/pool.py::Pool.check_tx_many": None,
                },
                "parity_tests": {"tests/test_parity.py": None},
            }
        }
    }))
    update_pins(root, pin_file)
    return root, pin_file


def test_twin_path_clean_after_pinning(tmp_path):
    root, pin_file = _twin_repo(tmp_path)
    assert TwinPathPass(pin_file).finalize(root) == []


def test_twin_path_twin_changed_without_parity_test(tmp_path):
    # change one twin, leave the parity test alone -> hard failure
    root, pin_file = _twin_repo(tmp_path)
    src = root / "pkg" / "pool.py"
    src.write_text(src.read_text().replace("tx * 1", "tx * 2", 1))
    out = TwinPathPass(pin_file).finalize(root)
    assert _rules(out) == ["twin-path"]
    assert "byte-identical" in out[0].message


def test_twin_path_paired_change_wants_repin_then_passes(tmp_path):
    root, pin_file = _twin_repo(tmp_path)
    (root / "pkg" / "pool.py").write_text(
        (root / "pkg" / "pool.py").read_text().replace("* 1", "* 2")
    )
    test_f = root / "tests" / "test_parity.py"
    test_f.write_text(test_f.read_text() + "def test_more(): pass\n")
    out = TwinPathPass(pin_file).finalize(root)
    assert _rules(out) == ["twin-path"]
    assert "--update-pins" in out[0].message
    update_pins(root, pin_file)
    assert TwinPathPass(pin_file).finalize(root) == []


def test_twin_path_missing_target(tmp_path):
    root, pin_file = _twin_repo(tmp_path)
    (root / "pkg" / "pool.py").write_text("class Pool:\n    pass\n")
    out = TwinPathPass(pin_file).finalize(root)
    assert _rules(out) == ["twin-path"]
    assert "not found" in out[0].message


def test_committed_pins_are_recorded():
    """The committed twins.json must carry real fingerprints (null pins
    would make the pass vacuous) and point at files that exist."""
    pins = json.loads(
        (REPO_ROOT / "txflow_tpu" / "analysis" / "twins.json").read_text()
    )
    assert pins["twins"], "no twin groups registered"
    for twin in pins["twins"].values():
        for spec, fp in twin["functions"].items():
            assert fp, f"unrecorded pin for {spec} — run tools/lint.py --update-pins"
            assert (REPO_ROOT / spec.partition("::")[0]).exists()
        for rel, fp in twin["parity_tests"].items():
            assert fp and (REPO_ROOT / rel).exists()


# ---------------------------------------------------------------------------
# host-sync
# ---------------------------------------------------------------------------

_DEVICE_SYNC_SRC = _src("""
    import numpy as np
    import jax.numpy as jnp

    def helper(x):
        y = jnp.sum(x)
        v = float(y)
        h = np.asarray(jnp.dot(x, x))
        x.block_until_ready()
        return v, h, x.item()
""")


def test_host_sync_device_values_in_hot_module():
    active, _ = lint_source(_DEVICE_SYNC_SRC, "txflow_tpu/engine/newmod.py")
    assert _rules(active) == ["host-sync"] * 4


def test_host_sync_taint_through_local_assignment():
    # device provenance survives a chain of local names
    active, _ = lint_source(_src("""
        import numpy as np
        import jax.numpy as jnp

        def f(x):
            a = jnp.sum(x)
            b = a
            return int(b)
    """), "txflow_tpu/parallel/newmod.py")
    assert _rules(active) == ["host-sync"]
    assert "int()" in active[0].message


def test_host_sync_host_data_is_clean():
    # np.asarray/float on HOST data is the normal prep path, not a sync
    active, _ = lint_source(_src("""
        import numpy as np

        def pack(val_idx, limbs):
            vi = np.asarray(val_idx, dtype=np.int64)
            return float(len(limbs)) + vi.sum()
    """), "txflow_tpu/ops/newmod.py")
    assert active == []


def test_host_sync_seams_and_cold_modules_exempt():
    # the staging ring's readback thread IS the sanctioned transfer
    active, _ = lint_source(_src("""
        import numpy as np
        import jax.numpy as jnp

        class StageSlot:
            def _run(self):
                self._host = np.asarray(jnp.asarray(self._dev))
    """), "txflow_tpu/parallel/staging.py")
    assert active == []
    active, _ = lint_source(_DEVICE_SYNC_SRC, "txflow_tpu/rpc/server.py")
    assert active == []


def test_host_sync_suppression_honored():
    active, suppressed = lint_source(_src("""
        import jax.numpy as jnp

        def warm(x):
            jnp.sum(x).block_until_ready()  # txlint: allow(host-sync) -- warmup path runs before serving
    """), "txflow_tpu/engine/newmod.py")
    assert active == []
    assert _rules(suppressed) == ["host-sync"]


# ---------------------------------------------------------------------------
# recompile-hazard
# ---------------------------------------------------------------------------


def test_recompile_hazard_raw_size_flagged():
    active, _ = lint_source(_src("""
        def dispatch(self, msgs):
            n = len(msgs)
            b = bucket_size(n, self.buckets)
            ok = tally.pack_step_inputs(batch, slot, b, None, b, q)
            bad = tally.pack_step_inputs(batch, slot, b, None, n, q)
            self.shapes_used.add(("fused", b, n))
    """), "txflow_tpu/verifier.py", [_passes().RecompileHazardPass()])
    assert _rules(active) == ["recompile-hazard"] * 2
    assert "pack_step_inputs size" in active[0].message
    assert "shapes_used" in active[1].message


def test_recompile_hazard_ladder_provenance_propagates():
    # bucket ladder -> locals -> arithmetic -> subscripts: all blessed
    active, _ = lint_source(_src("""
        def dispatch(self, msgs, full):
            n = len(msgs)
            b = bucket_size(n, self.buckets, multiple=self._n_shards)
            b_slots = self.buckets[0]
            limit = self.max_batch if full else bucket_size(n, self.buckets)
            rows = b + 0
            tally.pack_step_inputs(batch, slot, rows, None, b_slots, q)
            pack_step_inputs(batch, slot, min(limit, b), None, b, q)
            self.shapes_used.add(("verify", b, b_slots))
            for shape in self.enumerate_shapes(n):
                self.shapes_used.add(shape)
    """), "txflow_tpu/verifier.py", [_passes().RecompileHazardPass()])
    assert active == []


def test_recompile_hazard_out_of_scope_exempt():
    active, _ = lint_source(
        "def f(n):\n    pack_step_inputs(None, None, n, None, n, 0)\n",
        "txflow_tpu/node/node.py", [_passes().RecompileHazardPass()],
    )
    assert active == []


# ---------------------------------------------------------------------------
# seed-domain
# ---------------------------------------------------------------------------


def test_seed_domain_inline_literal_flagged():
    active, _ = lint_source(_src("""
        import hashlib

        def seed(s):
            return hashlib.sha256(b"mystream|%d" % s).digest()
    """), "txflow_tpu/newmod.py", [_passes().SeedDomainPass()])
    assert _rules(active) == ["seed-domain"]
    assert "utils.domains" in active[0].message


def test_seed_domain_joiner_and_plain_hashes_clean():
    # the b"|" joiner, |-prefixed format suffixes, and ordinary payload
    # hashing are not domain tags
    active, _ = lint_source(_src("""
        import hashlib
        from ..utils.domains import NETEM_LINK

        def seed(tag, s):
            h = hashlib.sha256()
            h.update(tag)
            h.update(b"|")
            h.update(s)
            hashlib.sha256(NETEM_LINK + b"|%d" % 3)
            return hashlib.sha256(b"ev-blockvote" + s).digest()
    """), "txflow_tpu/newmod.py", [_passes().SeedDomainPass()])
    assert active == []


def test_seed_domain_registry_duplicate_flagged():
    active, _ = lint_source(_src("""
        A = _register("a", b"one")
        B = _register("b", b"one")
        C = _register("a", b"two")
    """), "txflow_tpu/utils/domains.py", [_passes().SeedDomainPass()])
    rules = _rules(active)
    assert rules == ["seed-domain"] * 2
    assert "duplicate domain tag" in active[0].message
    assert "duplicate domain name" in active[1].message


# ---------------------------------------------------------------------------
# shared-decl
# ---------------------------------------------------------------------------


def test_shared_decl_annotation_required_and_validated():
    active, _ = lint_source(_src("""
        class C:
            def __init__(self):
                self._a = shared_field("c.a")
                self._b = shared_field("c.b")  # txlint: shared(self._mtx)
                self._c = shared_field("c.c")  # txlint: shared(banana)
                self._d = shared_field("c.d")  # txlint: shared(handoff)
                self.e = 1  # txlint: shared(self._mtx)
    """), "txflow_tpu/newmod.py", [_passes().SharedDeclPass()])
    msgs = {v.line: v.message for v in active}
    assert sorted(msgs) == [4, 6, 8]
    assert "without a" in msgs[4]
    assert "banana" in msgs[6]
    assert "dangling" in msgs[8]


def test_shared_decl_tree_declarations_all_annotated():
    # the committed tree's own declarations satisfy the pass (subset of
    # test_tree_is_clean, kept separate so a regression names the rule)
    report = lint_tree(REPO_ROOT)
    assert [v for v in report["violations"] if v.rule == "shared-decl"] == []


# ---------------------------------------------------------------------------
# stale-suppression (+ --prune-suppressions)
# ---------------------------------------------------------------------------


def test_stale_suppression_flagged_only_with_full_pass_set():
    src = _src("""
        def f():
            return 1  # txlint: allow(lock-blocking) -- nothing blocks here anymore
    """)
    active, _ = lint_source(src, "txflow_tpu/newmod.py")
    assert _rules(active) == ["stale-suppression"]
    # a subset run can't tell live from stale: no false positives
    active, _ = lint_source(src, "txflow_tpu/newmod.py", [_passes().HotPathPass()])
    assert active == []


def test_live_suppression_not_stale():
    active, suppressed = lint_source(_src("""
        class C:
            def send(self, frame):
                with self._mtx:
                    self.sock.sendall(frame)  # txlint: allow(lock-blocking) -- serializes whole-frame writes
    """), "txflow_tpu/newmod.py")
    assert active == []
    assert _rules(suppressed) == ["lock-blocking"]


def test_docstring_example_never_suppresses_or_goes_stale():
    active, _ = lint_source(_src('''
        """Docs: use  # txlint: allow(lock-blocking) -- why  to suppress."""

        def f():
            return 1
    '''), "txflow_tpu/newmod.py")
    assert active == []


def test_prune_suppressions_rewrites_stale_lines(tmp_path):
    # drive the CLI prune path against a scratch repo shaped like ours
    import subprocess as sp
    root = tmp_path / "repo"
    (root / "txflow_tpu").mkdir(parents=True)
    (root / "tools").mkdir()
    mod = root / "txflow_tpu" / "m.py"
    mod.write_text(
        "def f():\n"
        "    return 1  # txlint: allow(lock-blocking) -- stale by construction\n"
    )
    lint_py = (REPO_ROOT / "tools" / "lint.py").read_text().replace(
        "REPO_ROOT = Path(__file__).resolve().parent.parent",
        f"REPO_ROOT = Path({str(root)!r})\n"
        f"import sys; sys.path.insert(0, {str(REPO_ROOT)!r})",
    )
    (root / "tools" / "lint.py").write_text(lint_py)
    out = sp.run(
        [sys.executable, str(root / "tools" / "lint.py"), "--prune-suppressions"],
        capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "pruned 1 stale suppression(s)" in out.stdout
    assert mod.read_text() == "def f():\n    return 1\n"


# ---------------------------------------------------------------------------
# --json golden schema
# ---------------------------------------------------------------------------


def test_json_schema_matches_golden():
    """The --json output shape is a consumer contract (profile_host,
    bench lint stamp, CI): keys, violation fields, the rule inventory
    and documented exit codes are pinned by the golden file."""
    from txflow_tpu.analysis.core import RULES, report_to_json

    golden = json.loads(
        (REPO_ROOT / "tests" / "golden" / "lint_schema.json").read_text()
    )
    report = report_to_json(lint_tree(REPO_ROOT))
    assert sorted(report) == golden["top_level_keys"]
    assert sorted(RULES) == golden["rules"]
    for v in report["violations"] + report["suppressed"]:
        assert sorted(v) == golden["violation_keys"]
    assert isinstance(report["files_scanned"], int)
    assert all(isinstance(n, int) for n in report["counts"].values())
    assert golden["exit_codes"] == {
        "clean": 0, "check_violations": 1, "scan_errors": 2,
    }


def test_cli_race_report(tmp_path):
    import subprocess as sp
    dump = REPO_ROOT / ".race_audit.json"
    if not dump.exists():  # produced by any audited suite run
        dump.write_text(json.dumps({"fields": {}, "races": []}))
    out = sp.run(
        [sys.executable, str(REPO_ROOT / "tools" / "lint.py"), "--race-report"],
        capture_output=True, text=True,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "race audit:" in out.stdout


def _passes():
    from txflow_tpu.analysis import passes as _p
    return _p
