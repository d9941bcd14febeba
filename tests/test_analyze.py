"""The analysis plane analyzed: known-bad snippets for each static
checker in tools/analyze.py, and a synthetic two-thread ABBA ordering
the dynamic lock-graph detector must flag (while the clean ordering
stays silent — the real-suite guarantee is enforced globally by the
conftest session hook).

Also the tier-1 wiring: ``python tools/analyze.py --all`` must exit 0
over the repository as it stands.
"""

import os
import subprocess
import sys
import threading
import unittest
import unittest.mock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))
sys.path.insert(0, ROOT)

import analyze  # noqa: E402
from seaweedfs_tpu.util import config, locks  # noqa: E402

PKG_PATH = "seaweedfs_tpu/fake_module.py"


def problems_of(src: str, path: str = PKG_PATH):
    return analyze.analyze_source(src, path).problems


class TestEnvKnobChecker(unittest.TestCase):
    def test_raw_environ_get_flagged(self):
        src = 'import os\nv = os.environ.get("SW_FOO", "1")\n'
        probs = problems_of(src)
        self.assertTrue(any("env-knobs" in p and "SW_FOO" in p
                            for p in probs), probs)

    def test_raw_getenv_flagged(self):
        probs = problems_of('import os\nv = os.getenv("SW_BAR")\n')
        self.assertTrue(any("SW_BAR" in p for p in probs), probs)

    def test_subscript_read_flagged_write_allowed(self):
        read = problems_of('import os\nv = os.environ["SW_X"]\n')
        self.assertTrue(any("SW_X" in p for p in read), read)
        write = problems_of('import os\nos.environ["SW_X"] = "1"\n')
        self.assertFalse(any("SW_X" in p for p in write), write)

    def test_membership_test_flagged(self):
        probs = problems_of('import os\nb = "SW_Y" in os.environ\n')
        self.assertTrue(any("env_is_set" in p for p in probs), probs)

    def test_module_constant_name_resolved(self):
        src = ('import os\nKNOB = "SW_VIA_CONST"\n'
               'v = os.environ.get(KNOB)\n')
        probs = problems_of(src)
        self.assertTrue(any("SW_VIA_CONST" in p for p in probs), probs)

    def test_non_sw_env_ignored(self):
        probs = problems_of(
            'import os\nv = os.environ.get("JAX_PLATFORMS")\n')
        self.assertFalse(any("env-knobs" in p for p in probs), probs)

    def test_non_literal_accessor_flagged(self):
        src = ('from seaweedfs_tpu.util import config\n'
               'def f(n):\n    return config.env_int(n)\n')
        probs = problems_of(src)
        self.assertTrue(any("non-literal" in p for p in probs), probs)

    def test_accessor_reads_collected(self):
        src = ('from seaweedfs_tpu.util import config\n'
               'v = config.env_float("SW_PULSE_S")\n')
        rep = analyze.analyze_source(src, PKG_PATH)
        self.assertEqual(rep.problems, [])
        self.assertIn(("SW_PULSE_S", "env_float", 2), rep.knob_reads)

    def test_registry_kind_mismatch(self):
        probs = analyze.check_registry_coverage(
            [("SW_PULSE_S", "env_int", 1, PKG_PATH)])
        self.assertTrue(any("kind 'float'" in p for p in probs), probs)

    def test_registry_unregistered_read(self):
        probs = analyze.check_registry_coverage(
            [("SW_NOT_A_KNOB", "env_str", 1, PKG_PATH)])
        self.assertTrue(any("not registered" in p for p in probs),
                        probs)

    def test_allowlisted_raw_read_echoes_justification(self):
        # the allowlist is empty in the tree: the test brings its own
        entry = {("tools/drill.py", "SW_EC_DEGRADED_MODE"):
                 "save/restore of the raw env around a drill that "
                 "steers subprocess workers through inheritance"}
        with unittest.mock.patch.dict(analyze.ENV_RAW_ALLOWED, entry):
            rep = analyze.analyze_source(
                'import os\nv = os.environ.get("SW_EC_DEGRADED_MODE")\n',
                "tools/drill.py")
        self.assertEqual(rep.problems, [])
        self.assertTrue(any("allowed" in a and "subprocess" in a
                            for a in rep.allowed), rep.allowed)

    def test_env_table_lists_registered_knobs(self):
        table = config.env_table()
        for name in ("SW_PULSE_S", "SW_HTTP_POLL_S",
                     "SW_EC_GATHER_WINDOW", "SW_LOCK_DEBUG"):
            self.assertIn(name, table)

    def test_readme_table_fresh(self):
        self.assertEqual(analyze.check_readme_table(), [])


class TestLockDisciplineChecker(unittest.TestCase):
    def test_sleep_under_lock_flagged(self):
        src = ('import time\n'
               'def f(self):\n'
               '    with self._lock:\n'
               '        time.sleep(1)\n')
        probs = problems_of(src)
        self.assertTrue(any("lock-discipline" in p and "sleep" in p
                            for p in probs), probs)

    def test_network_call_under_lock_flagged(self):
        src = ('def f(self):\n'
               '    with self.lock:\n'
               '        return get_json("http://x/metrics")\n')
        probs = problems_of(src)
        self.assertTrue(any("network call" in p for p in probs), probs)

    def test_open_under_lock_flagged(self):
        src = ('def f(self):\n'
               '    with self._mu:\n'
               '        open("/tmp/x")\n')
        probs = problems_of(src)
        self.assertTrue(any("open()" in p for p in probs), probs)

    def test_sleep_outside_lock_clean(self):
        src = ('import time\n'
               'def f(self):\n'
               '    with self._lock:\n'
               '        x = 1\n'
               '    time.sleep(1)\n')
        self.assertFalse(
            [p for p in problems_of(src) if "lock-discipline" in p])

    def test_nested_def_not_flagged(self):
        # a closure defined under the lock runs later, outside it
        src = ('import time\n'
               'def f(self):\n'
               '    with self._lock:\n'
               '        def cb():\n'
               '            time.sleep(1)\n'
               '        self.cb = cb\n')
        self.assertFalse(
            [p for p in problems_of(src) if "lock-discipline" in p])

    def test_non_lock_context_ignored(self):
        src = ('import time\n'
               'def f(self):\n'
               '    with open("/tmp/x") as fh:\n'
               '        time.sleep(0.1)\n')
        self.assertFalse(
            [p for p in problems_of(src) if "lock-discipline" in p])

    def test_bare_threading_lock_flagged(self):
        src = ('import threading\nlock = threading.Lock()\n')
        probs = problems_of(src)
        self.assertTrue(any("make_lock" in p for p in probs), probs)
        src = ('import threading\nlock = threading.RLock()\n')
        probs = problems_of(src)
        self.assertTrue(any("make_rlock" in p for p in probs), probs)

    def test_factory_lock_clean(self):
        src = ('from ..util.locks import make_lock\n'
               'lock = make_lock("mod._lock")\n')
        self.assertFalse(
            [p for p in problems_of(src) if "lock-discipline" in p])

    def test_allowlisted_file_echoes_justification(self):
        src = ('def f(self):\n'
               '    with self.lock:\n'
               '        open("/x")\n')
        rep = analyze.analyze_source(
            src, "seaweedfs_tpu/storage/volume.py")
        self.assertFalse(
            [p for p in rep.problems if "lock-discipline" in p])
        self.assertTrue(any("atomic step" in a for a in rep.allowed),
                        rep.allowed)


class TestBackendIsolationChecker(unittest.TestCase):
    def test_jax_import_outside_ops_flagged(self):
        for src in ("import jax\n", "from jax import numpy\n",
                    "import jax.numpy as jnp\n"):
            probs = problems_of(src, "seaweedfs_tpu/storage/volume2.py")
            self.assertTrue(any("backend-isolation" in p
                                for p in probs), (src, probs))

    def test_jax_import_in_ops_allowed(self):
        probs = problems_of("import jax\n", "seaweedfs_tpu/ops/x.py")
        self.assertFalse(any("backend-isolation" in p for p in probs))

    def test_allowlisted_platform_shim_echoes(self):
        rep = analyze.analyze_source(
            "import jax\n", "seaweedfs_tpu/util/jax_platform.py")
        self.assertEqual(rep.problems, [])
        self.assertTrue(any("start-up rules for the device modules" in a
                            for a in rep.allowed), rep.allowed)


class TestThreadHygieneChecker(unittest.TestCase):
    def test_unnamed_thread_flagged(self):
        src = ('import threading\n'
               't = threading.Thread(target=print, daemon=True)\n'
               't.start()\n')
        probs = problems_of(src)
        self.assertTrue(any("unnamed thread" in p for p in probs),
                        probs)

    def test_named_daemon_thread_clean(self):
        src = ('import threading\n'
               't = threading.Thread(target=print, name="t", '
               'daemon=True)\n')
        self.assertFalse(
            [p for p in problems_of(src) if "thread-hygiene" in p])

    def test_non_daemon_thread_without_join_flagged(self):
        src = ('import threading\n'
               't = threading.Thread(target=print, name="t")\n'
               't.start()\n')
        probs = problems_of(src)
        self.assertTrue(any("non-daemon" in p for p in probs), probs)

    def test_non_daemon_thread_with_join_clean(self):
        src = ('import threading\n'
               't = threading.Thread(target=print, name="t")\n'
               't.start()\nt.join()\n')
        self.assertFalse(
            [p for p in problems_of(src) if "non-daemon" in p])

    def test_bare_except_flagged(self):
        src = ('try:\n    x = 1\nexcept:\n    pass\n')
        probs = problems_of(src)
        self.assertTrue(any("bare 'except:'" in p for p in probs),
                        probs)


class TestLockOrderDetector(unittest.TestCase):
    """Synthetic ABBA: thread 1 takes A then B, thread 2 takes B then
    A.  Sequenced (t2 starts after t1 finished) so the test can never
    actually deadlock — the graph still shows the cycle, which is the
    point: the hazard is the ordering, not a lucky interleaving."""

    def _run_order(self, rec, first, second):
        def body():
            with first:
                with second:
                    pass
        t = threading.Thread(target=body, name="order-probe")
        t.start()
        t.join(10)
        self.assertFalse(t.is_alive())

    def test_abba_cycle_detected(self):
        rec = locks.LockGraphRecorder()
        a = locks.make_lock("fixture.A", recorder=rec)
        b = locks.make_lock("fixture.B", recorder=rec)
        self._run_order(rec, a, b)
        self._run_order(rec, b, a)
        cycles = rec.cycles()
        self.assertEqual(cycles, [["fixture.A", "fixture.B"]])

    def test_consistent_order_is_silent(self):
        rec = locks.LockGraphRecorder()
        a = locks.make_lock("fixture.A", recorder=rec)
        b = locks.make_lock("fixture.B", recorder=rec)
        self._run_order(rec, a, b)
        self._run_order(rec, a, b)
        self.assertEqual(rec.cycles(), [])

    def test_allowed_edge_suppresses_cycle(self):
        rec = locks.LockGraphRecorder()
        a = locks.make_lock("fixture.A", recorder=rec)
        b = locks.make_lock("fixture.B", recorder=rec)
        self._run_order(rec, a, b)
        self._run_order(rec, b, a)
        self.assertEqual(
            rec.cycles(allowed={("fixture.B", "fixture.A")}), [])

    def test_three_way_cycle(self):
        rec = locks.LockGraphRecorder()
        a = locks.make_lock("fixture.A", recorder=rec)
        b = locks.make_lock("fixture.B", recorder=rec)
        c = locks.make_lock("fixture.C", recorder=rec)
        self._run_order(rec, a, b)
        self._run_order(rec, b, c)
        self._run_order(rec, c, a)
        self.assertEqual(rec.cycles(),
                         [["fixture.A", "fixture.B", "fixture.C"]])

    def test_rlock_reentrancy_no_self_edge(self):
        rec = locks.LockGraphRecorder()
        r = locks.make_rlock("fixture.R", recorder=rec)
        with r:
            with r:
                pass
        self.assertEqual(rec.edge_list(), [])

    def test_condition_protocol_keeps_stack_sane(self):
        rec = locks.LockGraphRecorder()
        r = locks.make_rlock("fixture.R", recorder=rec)
        cond = threading.Condition(r)
        hit = []

        def waiter():
            with cond:
                hit.append("waiting")
                cond.wait(timeout=5)
                hit.append("woke")

        t = threading.Thread(target=waiter, name="cond-waiter")
        t.start()
        deadline = 50
        while not hit and deadline:
            deadline -= 1
            threading.Event().wait(0.05)
        with cond:
            cond.notify_all()
        t.join(10)
        self.assertEqual(hit, ["waiting", "woke"])
        # wait() released and re-acquired; no spurious edges appear
        self.assertEqual(rec.cycles(), [])

    def test_dump_and_merge(self):
        import tempfile
        rec = locks.LockGraphRecorder()
        a = locks.make_lock("fixture.A", recorder=rec)
        b = locks.make_lock("fixture.B", recorder=rec)
        self._run_order(rec, a, b)
        d = tempfile.mkdtemp(prefix="lockgraph_test_")
        rec.dump(os.path.join(d, "lockgraph-1.json"))
        merged = locks.load_graph_dir(d)
        self.assertEqual(len(merged), 1)
        self.assertEqual((merged[0]["from"], merged[0]["to"]),
                         ("fixture.A", "fixture.B"))
        # a reverse edge arriving from another process's dump closes
        # the cycle in the MERGED graph
        other = locks.LockGraphRecorder()
        a2 = locks.make_lock("fixture.A", recorder=other)
        b2 = locks.make_lock("fixture.B", recorder=other)
        self._run_order(other, b2, a2)
        other.dump(os.path.join(d, "lockgraph-2.json"))
        rec2 = locks.LockGraphRecorder()
        cycles = rec2.cycles(extra_edges=locks.load_graph_dir(d))
        self.assertEqual(cycles, [["fixture.A", "fixture.B"]])


class TestAnalyzeAllTier1(unittest.TestCase):
    def test_analyze_all_clean(self):
        """tools/analyze.py --all must exit 0 over the repo (tier-1)."""
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "analyze.py"),
             "--all", "--quiet"],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        self.assertEqual(proc.returncode, 0,
                         proc.stdout + "\n" + proc.stderr)
        self.assertIn("clean", proc.stdout)

    def test_env_table_mode(self):
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "tools", "analyze.py"),
             "--env-table"],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        self.assertEqual(proc.returncode, 0, proc.stderr)
        self.assertIn("SW_PULSE_S", proc.stdout)
        self.assertIn("| Variable |", proc.stdout)


if __name__ == "__main__":
    unittest.main()
