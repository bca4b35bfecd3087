"""s2c2lint: per-rule positive/negative fixtures, suppressions, baseline,
CLI, and the self-check that the live cluster tree is clean.

Fixture modules are written to tmp_path and analyzed in isolation, so
every rule's firing condition is pinned independently of the real tree.
"""

import json
import pathlib
import textwrap

from repro.analysis import Baseline, analyze
from repro.analysis.__main__ import main

REPO = pathlib.Path(__file__).resolve().parents[1]


def lint(tmp_path, files, select=None):
    """Write {name: source} modules and analyze the directory."""
    for name, source in files.items():
        (tmp_path / name).write_text(textwrap.dedent(source))
    findings, _ = analyze([str(tmp_path)], select=select)
    return findings


def rules_of(findings):
    return sorted(f.rule for f in findings)


class TestGuardedBy:
    GOOD_AND_BAD = """
        import threading

        class Box:
            def __init__(self):
                self.items = []        # guarded_by: _lock
                self._lock = threading.Lock()

            def bad(self):
                return len(self.items)

            def good(self):
                with self._lock:
                    return len(self.items)
        """

    def test_unguarded_access_fires_and_guarded_does_not(self, tmp_path):
        found = lint(tmp_path, {"box.py": self.GOOD_AND_BAD},
                     select=["S2C201"])
        assert rules_of(found) == ["S2C201"]
        assert "bad" in found[0].message
        assert "without holding it" in found[0].message

    def test_init_is_exempt(self, tmp_path):
        src = """
            import threading

            class Box:
                def __init__(self):
                    self.items = []        # guarded_by: _lock
                    self._lock = threading.Lock()
                    self.items.append(1)   # construction precedes sharing
        """
        assert lint(tmp_path, {"box.py": src}, select=["S2C201"]) == []

    def test_thread_confinement(self, tmp_path):
        src = """
            class Driver:
                def __init__(self):
                    # guarded_by: thread:driver
                    self.pending = {}

                # thread: driver
                def ok(self):
                    self.pending.clear()

                def bad(self):
                    self.pending.clear()
        """
        found = lint(tmp_path, {"driver.py": src}, select=["S2C201"])
        assert rules_of(found) == ["S2C201"]
        assert "confined to thread 'driver'" in found[0].message
        assert "bad" in found[0].message

    def test_annotated_param_resolves_across_classes(self, tmp_path):
        src = """
            import threading

            class Ledger:
                def __init__(self):
                    self.rows = {}         # guarded_by: _lock
                    self._lock = threading.Lock()

            class User:
                def bad(self, ledger: Ledger):
                    return ledger.rows

                def good(self, ledger: Ledger):
                    with ledger._lock:
                        return ledger.rows
        """
        found = lint(tmp_path, {"ledger.py": src}, select=["S2C201"])
        assert len(found) == 1 and "bad" in found[0].message


class TestLockOrder:
    def test_inverted_order_is_a_cycle(self, tmp_path):
        src = """
            import threading

            class A:
                def __init__(self):
                    self._a_lock = threading.Lock()
                    self._b_lock = threading.Lock()

                def one(self):
                    with self._a_lock:
                        with self._b_lock:
                            pass

                def two(self):
                    with self._b_lock:
                        with self._a_lock:
                            pass
        """
        found = lint(tmp_path, {"locks.py": src}, select=["S2C202"])
        assert rules_of(found) == ["S2C202"]
        assert "lock-order cycle" in found[0].message

    def test_consistent_order_is_clean(self, tmp_path):
        src = """
            import threading

            class A:
                def __init__(self):
                    self._a_lock = threading.Lock()
                    self._b_lock = threading.Lock()

                def one(self):
                    with self._a_lock:
                        with self._b_lock:
                            pass

                def two(self):
                    with self._a_lock:
                        with self._b_lock:
                            pass
        """
        assert lint(tmp_path, {"locks.py": src}, select=["S2C202"]) == []

    def test_reacquisition_deadlock(self, tmp_path):
        src = """
            import threading

            class A:
                def __init__(self):
                    self._lock = threading.Lock()

                def re(self):
                    with self._lock:
                        with self._lock:
                            pass
        """
        found = lint(tmp_path, {"re.py": src}, select=["S2C202"])
        assert len(found) == 1
        assert "nested acquisition" in found[0].message


class TestBlockingUnderLock:
    def test_sleep_under_lock_fires(self, tmp_path):
        src = """
            import threading
            import time

            class S:
                def __init__(self):
                    self._lock = threading.Lock()

                def bad(self):
                    with self._lock:
                        time.sleep(0.1)

                def good(self):
                    time.sleep(0.1)
                    with self._lock:
                        pass
        """
        found = lint(tmp_path, {"s.py": src}, select=["S2C203"])
        assert rules_of(found) == ["S2C203"]
        assert "time.sleep" in found[0].message

    def test_cv_wait_is_not_blocking(self, tmp_path):
        # cv.wait releases the lock it waits under — the one blocking
        # call that is CORRECT under a lock
        src = """
            import threading

            class S:
                def __init__(self):
                    self._cv = threading.Condition()

                def ok(self):
                    with self._cv:
                        self._cv.wait(1.0)
        """
        assert lint(tmp_path, {"s.py": src}, select=["S2C203"]) == []


class TestTracerGuard:
    def test_unguarded_emit_and_alias(self, tmp_path):
        src = """
            class T:
                def __init__(self, tracer):
                    self.tracer = tracer

                def bad(self):
                    self.tracer.emit("x", a=1)

                def good(self):
                    if self.tracer.enabled:
                        self.tracer.emit("x", a=1)

                def alias_good(self):
                    if self.tracer.enabled:
                        emit = self.tracer.emit
                        emit("y")

                def alias_bad(self):
                    emit = self.tracer.emit
                    emit("y")
        """
        found = lint(tmp_path, {"t.py": src}, select=["S2C204"])
        # bad() emit + alias_bad() binding + alias_bad() aliased call
        assert rules_of(found) == ["S2C204"] * 3
        msgs = " | ".join(f.message for f in found)
        assert "binding of tracer.emit" in msgs
        assert "alias" in msgs

    def test_obs_py_is_exempt(self, tmp_path):
        src = """
            class Tracer:
                def drain(self):
                    self.tracer.emit("x")
        """
        assert lint(tmp_path, {"obs.py": src}, select=["S2C204"]) == []

    def test_obs_py_helpers_are_checked(self, tmp_path):
        """Only the Tracer sink is exempt: a helper in obs.py that emits
        into a tracer (the span helper) needs the guard like any site."""
        src = """
            class span:
                def __exit__(self, *exc):
                    tracer = self.tracer
                    tracer.emit(self.kind)

            class guarded:
                def __exit__(self, *exc):
                    tracer = self.tracer
                    if tracer is not None and tracer.enabled:
                        tracer.emit(self.kind)
        """
        found = lint(tmp_path, {"obs.py": src}, select=["S2C204"])
        assert rules_of(found) == ["S2C204"]
        assert "'__exit__'" in found[0].message and found[0].line == 5


# a minimal, fully consistent wire protocol — the S2C205 happy path
TRANSPORT_OK = """
    import dataclasses


    class WireSpec:
        def __init__(self, direction, protected=False):
            self.direction = direction
            self.protected = protected


    @dataclasses.dataclass
    class _Ping:
        x: int


    @dataclasses.dataclass
    class _Pong:
        x: int


    WIRE_PROTOCOL = {
        _Ping: WireSpec("m2c", protected=True),
        _Pong: WireSpec("c2m"),
    }

    _PROTECTED = tuple(c for c, s in WIRE_PROTOCOL.items() if s.protected)


    class MasterEndpoint:
        def on_msg(self, msg):
            if isinstance(msg, _Pong):
                pass

        def send(self):
            self._send(_Ping(1))


    class _ChildNode:
        def on_msg(self, msg):
            if isinstance(msg, _Ping):
                pass

        def reply(self):
            self._send(_Pong(2))


    class Chaos:
        def route(self, msg):
            if isinstance(msg, _PROTECTED):
                return True
"""


class TestWireProtocol:
    def test_consistent_protocol_is_clean(self, tmp_path):
        assert lint(tmp_path, {"transport.py": TRANSPORT_OK},
                    select=["S2C205"]) == []

    def test_sent_but_unregistered_frame(self, tmp_path):
        src = TRANSPORT_OK.replace("    _Pong: WireSpec(\"c2m\"),\n", "")
        found = lint(tmp_path, {"transport.py": src}, select=["S2C205"])
        msgs = " | ".join(f.message for f in found)
        assert "'_Pong' is constructed/sent but not registered" in msgs

    def test_registered_frame_without_handler(self, tmp_path):
        src = TRANSPORT_OK.replace(
            "            if isinstance(msg, _Pong):\n"
            "                pass",
            "            pass")
        found = lint(tmp_path, {"transport.py": src}, select=["S2C205"])
        assert any("no isinstance handler on the master side" in f.message
                   for f in found)

    def test_hand_listed_protected_diverges(self, tmp_path):
        src = TRANSPORT_OK.replace(
            "_PROTECTED = tuple(c for c, s in WIRE_PROTOCOL.items() "
            "if s.protected)",
            "_PROTECTED = (_Ping,)")
        found = lint(tmp_path, {"transport.py": src}, select=["S2C205"])
        assert any("hand-listed instead of derived" in f.message
                   for f in found)

    def test_worker_event_without_master_handler(self, tmp_path):
        worker = """
            import dataclasses


            @dataclasses.dataclass
            class _Done:
                chunk: int


            class Worker:
                def report(self):
                    self.events.put(_Done(1))
        """
        master_ok = """
            class Collector:
                def collect(self, ev):
                    if isinstance(ev, _Done):
                        pass
        """
        found = lint(tmp_path, {"transport.py": TRANSPORT_OK,
                                "worker.py": worker,
                                "master.py": master_ok},
                     select=["S2C205"])
        assert found == []
        found = lint(tmp_path, {"master.py": "class Collector:\n    pass\n"},
                     select=["S2C205"])
        assert any("'_Done' is emitted but has no" in f.message
                   for f in found)


# fenced-frame variant: _Ping carries the epoch token and the child
# handler checks it — the S2C205 fencing happy path
TRANSPORT_FENCED = """
    import dataclasses


    class WireSpec:
        def __init__(self, direction, protected=False, fenced=False):
            self.direction = direction
            self.protected = protected
            self.fenced = fenced


    @dataclasses.dataclass
    class _Ping:
        x: int
        epoch: int


    @dataclasses.dataclass
    class _Pong:
        x: int


    WIRE_PROTOCOL = {
        _Ping: WireSpec("m2c", protected=True, fenced=True),
        _Pong: WireSpec("c2m"),
    }

    _PROTECTED = tuple(c for c, s in WIRE_PROTOCOL.items() if s.protected)


    class MasterEndpoint:
        def on_msg(self, msg):
            if isinstance(msg, _Pong):
                pass

        def send(self):
            self._send(_Ping(1, 1))


    class _ChildNode:
        epoch = 0

        def on_msg(self, msg):
            if isinstance(msg, _Ping):
                if msg.epoch < self.epoch:
                    return

        def reply(self):
            self._send(_Pong(2))


    class Chaos:
        def route(self, msg):
            if isinstance(msg, _PROTECTED):
                return True
"""


class TestFencedFrames:
    def test_fenced_protocol_is_clean(self, tmp_path):
        assert lint(tmp_path, {"transport.py": TRANSPORT_FENCED},
                    select=["S2C205"]) == []

    def test_fenced_frame_without_epoch_field(self, tmp_path):
        src = TRANSPORT_FENCED.replace(
            "        x: int\n        epoch: int", "        x: int", 1)
        found = lint(tmp_path, {"transport.py": src}, select=["S2C205"])
        assert any("declares no 'epoch' field" in f.message for f in found)

    def test_fenced_frame_accepted_without_epoch_check(self, tmp_path):
        src = TRANSPORT_FENCED.replace(
            "            if isinstance(msg, _Ping):\n"
            "                if msg.epoch < self.epoch:\n"
            "                    return",
            "            if isinstance(msg, _Ping):\n"
            "                pass")
        found = lint(tmp_path, {"transport.py": src}, select=["S2C205"])
        assert any("without an epoch comparison" in f.message
                   for f in found)


JOURNAL_OK = """
    JOURNAL_KINDS = {
        "meta": "identity",
        "ack": "collected chunk",
    }


    class RoundJournal:
        def append_record(self, kind, payload):
            if kind not in JOURNAL_KINDS:
                raise ValueError(kind)

        @classmethod
        def replay(cls, path):
            for rec in []:
                kind = rec.get("kind")
                if kind == "meta":
                    pass
                elif kind == "ack":
                    pass
"""

MASTER_JOURNALS = """
    class Engine:
        def collect(self):
            self._journal("ack", {"chunk": 1})
"""


class TestJournalKinds:
    def test_consistent_journal_is_clean(self, tmp_path):
        found = lint(tmp_path, {"transport.py": TRANSPORT_OK,
                                "journal.py": JOURNAL_OK,
                                "master.py": MASTER_JOURNALS},
                     select=["S2C205"])
        assert found == []

    def test_unregistered_kind_at_append_site(self, tmp_path):
        master = MASTER_JOURNALS.replace('"ack"', '"bogus"')
        found = lint(tmp_path, {"transport.py": TRANSPORT_OK,
                                "journal.py": JOURNAL_OK,
                                "master.py": master},
                     select=["S2C205"])
        assert any("'bogus' is appended but not registered" in f.message
                   for f in found)

    def test_registered_kind_never_folded_by_replay(self, tmp_path):
        journal = JOURNAL_OK.replace(
            "                elif kind == \"ack\":\n"
            "                    pass\n", "")
        found = lint(tmp_path, {"transport.py": TRANSPORT_OK,
                                "journal.py": journal,
                                "master.py": MASTER_JOURNALS},
                     select=["S2C205"])
        assert any("'ack' is registered but never folded" in f.message
                   for f in found)


class TestSuppressions:
    BAD = """
        import threading

        class Box:
            def __init__(self):
                self.items = []        # guarded_by: _lock
                self._lock = threading.Lock()

            def bad(self):
                return len(self.items){suffix}
    """

    def test_inline_ignore_with_reason(self, tmp_path):
        src = self.BAD.format(
            suffix="  # s2c2lint: ignore[S2C201] snapshot read is benign")
        assert lint(tmp_path, {"b.py": src}, select=["S2C201"]) == []

    def test_reasonless_ignore_is_itself_a_finding(self, tmp_path):
        src = self.BAD.format(suffix="  # s2c2lint: ignore[S2C201]")
        found = lint(tmp_path, {"b.py": src}, select=["S2C201"])
        assert len(found) == 1
        assert "suppression without a reason" in found[0].message

    def test_own_line_ignore_targets_next_source_line(self, tmp_path):
        src = """
            import threading

            class Box:
                def __init__(self):
                    self.items = []        # guarded_by: _lock
                    self._lock = threading.Lock()

                def bad(self):
                    # s2c2lint: ignore[S2C201] benign racy length probe
                    return len(self.items)
        """
        assert lint(tmp_path, {"b.py": src}, select=["S2C201"]) == []

    def test_parse_error_is_a_finding_not_a_crash(self, tmp_path):
        found = lint(tmp_path, {"broken.py": "def oops(:\n"})
        assert rules_of(found) == ["S2C200"]


class TestBaseline:
    def test_round_trip_suppresses_everything(self, tmp_path):
        found = lint(tmp_path, {"b.py": TestSuppressions.BAD.format(suffix="")},
                     select=["S2C201"])
        assert len(found) == 1
        bl_path = tmp_path / "bl.json"
        Baseline.from_findings(found, reason="accepted debt").save(
            str(bl_path))
        loaded = Baseline.load(str(bl_path))
        live, stale = loaded.apply(found)
        assert live == [] and stale == []

    def test_fingerprint_survives_line_moves(self, tmp_path):
        found = lint(tmp_path, {"b.py": TestSuppressions.BAD.format(suffix="")},
                     select=["S2C201"])
        baseline = Baseline.from_findings(found)
        # shift the finding down two lines: same fingerprint, new lineno
        moved = lint(tmp_path, {"b.py": "\n\n" +
                                textwrap.dedent(
                                    TestSuppressions.BAD.format(suffix=""))},
                     select=["S2C201"])
        assert moved[0].line != found[0].line
        live, stale = baseline.apply(moved)
        assert live == [] and stale == []

    def test_stale_entries_are_reported(self, tmp_path):
        baseline = Baseline([{"rule": "S2C201", "path": "gone.py",
                              "message": "fixed long ago", "reason": "x"}])
        live, stale = baseline.apply([])
        assert live == [] and len(stale) == 1


class TestCLI:
    def _fixture(self, tmp_path):
        d = tmp_path / "pkg"
        d.mkdir()
        (d / "b.py").write_text(
            textwrap.dedent(TestSuppressions.BAD.format(suffix="")))
        return d

    def test_exit_codes_and_json_report(self, tmp_path):
        d = self._fixture(tmp_path)
        report = tmp_path / "report.json"
        assert main([str(d), "--json", str(report)]) == 1
        doc = json.loads(report.read_text())
        assert doc["tool"] == "s2c2lint"
        assert doc["counts"] == {"S2C201": 1}
        assert doc["findings"][0]["rule"] == "S2C201"

    def test_write_baseline_then_clean(self, tmp_path):
        d = self._fixture(tmp_path)
        bl = tmp_path / "bl.json"
        assert main([str(d), "--write-baseline", "--baseline",
                     str(bl)]) == 0
        assert main([str(d), "--baseline", str(bl)]) == 0

    def test_unknown_path_and_rule_are_usage_errors(self, tmp_path):
        assert main([str(tmp_path / "nope")]) == 2
        d = self._fixture(tmp_path)
        assert main([str(d), "--select", "S2C999"]) == 2

    def test_list_rules(self, capsys):
        assert main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for rid in ("S2C201", "S2C202", "S2C203", "S2C204", "S2C205"):
            assert rid in out


class TestLiveTree:
    def test_cluster_package_is_clean(self):
        """The acceptance self-check: the shipped tree carries no
        un-baselined findings (and the committed baseline is empty)."""
        findings, project = analyze([str(REPO / "src" / "repro" / "cluster")])
        assert findings == [], "\n".join(
            f"{f.path}:{f.line}: {f.rule} {f.message}" for f in findings)
        assert len(project.files) >= 8

    def test_committed_baseline_is_empty(self):
        doc = json.loads((REPO / ".s2c2lint-baseline.json").read_text())
        assert doc == {"version": 1, "suppressions": []}
