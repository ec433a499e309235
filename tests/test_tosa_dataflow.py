"""Fixture tests for the project-wide (phase-2) tosa rules.

Each rule family gets bad-fixture-fires / good-fixture-stays-clean pairs,
plus the cross-rule interaction coverage ISSUE 9 asks for: block-scoped
suppressions and baseline fingerprints for project-level findings.
"""

import os
import textwrap
import unittest

from tosa_testutil import LIB_PATH, REPO_ROOT, core, run_project_rule


def _src(body):
    return textwrap.dedent(body).strip() + "\n"


# ---------------------------------------------------------------------------
# donation-safety
# ---------------------------------------------------------------------------

#: the PR 7 ckpt/snapshot.py bug, reduced: jax's cached sharded-array
#: assembly (read-only host memory) pooled as a reusable writable buffer
SNAPSHOT_POOL_BUG = _src(
    """
    import jax
    import numpy as np

    class SnapshotBuffers:
        def __init__(self):
            self._free = []

        def take(self, leaf):
            host = jax.device_get(leaf)
            arr = np.asarray(host)
            self._free.append(arr)
            return arr
    """
)


class TestDonationSafety(unittest.TestCase):
    def test_pr7_snapshot_pool_bug_fires(self):
        findings = run_project_rule("donation-safety", {LIB_PATH: SNAPSHOT_POOL_BUG})
        self.assertEqual(len(findings), 1)
        self.assertEqual(findings[0].rule, "donation-safety")
        self.assertIn("jax.device_get", findings[0].message)
        self.assertIn("_free", findings[0].message)

    def test_owned_copy_stays_clean(self):
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax
            import numpy as np

            class SnapshotBuffers:
                def __init__(self):
                    self._free = []

                def take(self, leaf):
                    host = jax.device_get(leaf)
                    arr = np.array(host, copy=True)
                    self._free.append(arr)
                    return arr
            """
        )})
        self.assertEqual(findings, [])

    def test_flags_check_sanitizes(self):
        # the shape of the in-tree fix: checking .flags before pooling
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax
            import numpy as np

            class SnapshotBuffers:
                def __init__(self):
                    self._free = []

                def take(self, leaf):
                    arr = np.asarray(jax.device_get(leaf))
                    if not arr.flags.owndata or not arr.flags.writeable:
                        arr = np.array(arr, copy=True)
                    self._free.append(arr)
                    return arr
            """
        )})
        self.assertEqual(findings, [])

    def test_owndata_only_guard_does_not_sanitize(self):
        # the exact shape of the PRE-fix PR 7 guard: an early return copies
        # when owndata is false, but jax's cached sharded assembly OWNS its
        # data and is still frozen — the fallthrough returns the raw view,
        # and only a .flags.writeable check counts as handling that case
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax
            import numpy as np

            def _leaf_to_host(leaf):
                arr = np.asarray(jax.device_get(leaf))
                if not arr.flags.owndata:
                    return np.array(arr, copy=True)
                return arr

            class SnapshotBuffers:
                def __init__(self):
                    self._free = []

                def take(self, leaf):
                    arr = _leaf_to_host(leaf)
                    self._free.append(arr)
                    return arr
            """
        )})
        self.assertEqual(len(findings), 1)
        self.assertIn("_free", findings[0].message)

    def test_inplace_write_of_device_view_fires(self):
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax
            import numpy as np

            def refresh(out, leaf):
                view = jax.device_get(leaf)
                view[0] = 0.0
                return view
            """
        )})
        self.assertEqual(len(findings), 1)
        self.assertIn("in place", findings[0].message)

    def test_copyto_into_tainted_destination_fires(self):
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax
            import numpy as np

            def refresh(leaf, fresh):
                dst = np.asarray(jax.device_get(leaf))
                np.copyto(dst, fresh)
                return dst
            """
        )})
        self.assertEqual(len(findings), 1)
        self.assertIn("copyto", findings[0].message)

    def test_taint_flows_through_helper_return(self):
        # cross-function propagation: the helper's return is device-derived
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax
            import numpy as np

            def _to_host(leaf):
                return np.asarray(jax.device_get(leaf))

            class Pool:
                def __init__(self):
                    self._slots = []

                def keep(self, leaf):
                    arr = _to_host(leaf)
                    self._slots.append(arr)
            """
        )})
        self.assertEqual(len(findings), 1)
        self.assertIn("_to_host", findings[0].message)

    def test_read_after_donation_fires(self):
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax

            step = jax.jit(lambda s, b: s, donate_argnums=(0,))

            def run(state, batch):
                out = step(state, batch)
                return state
            """
        )})
        self.assertEqual(len(findings), 1)
        self.assertIn("donated", findings[0].message)
        self.assertIn("step", findings[0].message)

    def test_rebind_idiom_stays_clean(self):
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax

            step = jax.jit(lambda s, b: s, donate_argnums=(0,))

            def run(state, batches):
                for batch in batches:
                    state = step(state, batch)
                return state
            """
        )})
        self.assertEqual(findings, [])

    def test_non_donated_args_stay_readable(self):
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax

            step = jax.jit(lambda s, b: s, donate_argnums=(0,))

            def run(state, batch):
                state = step(state, batch)
                return state, batch
            """
        )})
        self.assertEqual(findings, [])


class TestDonatedBufferHandedOutAgain(unittest.TestCase):
    """A program that donates a buffer it hands out again: a grad program
    that donated its params would invalidate the buffers every later
    microbatch (and whatever still holds the first's gradients) reads."""

    def test_donating_grad_fn_fires(self):
        # donate params to the grad program, then keep handing them out for
        # the next microbatch while the first's grads sit on a queue
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax

            def dispatch(loss_fn, params, b1, jobs):
                gfn = jax.jit(jax.value_and_grad(loss_fn), donate_argnums=(0,))
                loss1, g1 = gfn(params, b1)
                jobs.put(g1)
                return params
            """
        )})
        self.assertEqual(len(findings), 1)
        self.assertIn("read after being donated", findings[0].message)

    def test_donating_only_the_rebound_state_stays_clean(self):
        # the grad program donates nothing; only the apply program donates,
        # after the last read of what it takes, and its result is rebound
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax

            def dispatch(loss_fn, apply, params, opt_state, b1, b2, jobs):
                gfn = jax.jit(jax.value_and_grad(loss_fn), donate_argnums=())
                loss1, g1 = gfn(params, b1)
                jobs.put(g1)
                loss2, g2 = gfn(params, b2)
                jobs.put(g2)
                apply_fn = jax.jit(apply, donate_argnums=(0, 1))
                params, opt_state = apply_fn(params, opt_state, g1)
                return params, opt_state, loss2
            """
        )})
        self.assertEqual(findings, [])

    def test_in_tree_scheduler_stays_clean(self):
        # the rule over the real module: the shipped scheduler never reads
        # a donated buffer (grad fns donate nothing, apply rebinds)
        path = os.path.join(
            REPO_ROOT, "tensorflowonspark_tpu", "train", "strategy.py"
        )
        with open(path) as f:
            src = f.read()
        findings = run_project_rule(
            "donation-safety", {"tensorflowonspark_tpu/train/strategy.py": src}
        )
        self.assertEqual(findings, [])


# ---------------------------------------------------------------------------
# metrics-contract
# ---------------------------------------------------------------------------

GOOD_DOCS = {
    "docs/architecture.md": _src(
        """
        ### Metrics inventory

        | name | kind | meaning |
        | --- | --- | --- |
        | `good_things_total` | counter | things that went well |
        """
    )
}


class TestMetricsContract(unittest.TestCase):
    def test_documented_conforming_counter_is_clean(self):
        findings = run_project_rule("metrics-contract", {LIB_PATH: _src(
            """
            from tensorflowonspark_tpu import obs

            def work():
                obs.counter("good_things_total", help="x").inc()
            """
        )}, docs=GOOD_DOCS)
        self.assertEqual(findings, [])

    def test_counter_without_total_suffix_fires(self):
        findings = run_project_rule("metrics-contract", {LIB_PATH: _src(
            """
            from tensorflowonspark_tpu import obs

            def work():
                obs.counter("good_things", help="x").inc()
            """
        )})
        self.assertEqual(len(findings), 1)
        self.assertIn("_total", findings[0].message)

    def test_gauge_with_total_suffix_fires(self):
        findings = run_project_rule("metrics-contract", {LIB_PATH: _src(
            """
            from tensorflowonspark_tpu import obs

            def work():
                obs.gauge("queue_depth_total", help="x").set(1)
            """
        )})
        self.assertEqual(len(findings), 1)
        self.assertIn("reserved for counters", findings[0].message)

    def test_dynamic_name_outside_obs_fires(self):
        findings = run_project_rule("metrics-contract", {LIB_PATH: _src(
            """
            from tensorflowonspark_tpu import obs

            def work(kind):
                obs.counter("x_{}_total".format(kind), help="x").inc()
            """
        )})
        self.assertEqual(len(findings), 1)
        self.assertIn("non-literal", findings[0].message)

    def test_desynced_docs_fire_both_directions(self):
        # registered-but-undocumented AND documented-but-unregistered
        findings = run_project_rule("metrics-contract", {LIB_PATH: _src(
            """
            from tensorflowonspark_tpu import obs

            def work():
                obs.counter("undocumented_total", help="x").inc()
            """
        )}, docs=GOOD_DOCS)
        messages = sorted(f.message for f in findings)
        self.assertEqual(len(findings), 2)
        self.assertIn("undocumented_total", messages[1])
        self.assertIn("missing from the Metrics inventory", messages[1])
        self.assertIn("good_things_total", messages[0])
        self.assertIn("never registered", messages[0])
        # the stale-row finding anchors at the docs file
        stale = [f for f in findings if "never registered" in f.message][0]
        self.assertEqual(stale.path, "docs/architecture.md")

    def test_kind_mismatch_fires(self):
        findings = run_project_rule("metrics-contract", {LIB_PATH: _src(
            """
            from tensorflowonspark_tpu import obs

            def work():
                obs.gauge("good_things_total").set(1)
            """
        )}, docs=GOOD_DOCS)
        # the gauge-named-_total conformance finding plus the kind mismatch
        mismatch = [f for f in findings if "documented as a" in f.message]
        self.assertEqual(len(mismatch), 1)
        self.assertEqual(mismatch[0].path, "docs/architecture.md")

    def test_unmerged_private_registry_fires(self):
        findings = run_project_rule("metrics-contract", {LIB_PATH: _src(
            """
            from tensorflowonspark_tpu.obs import registry as obs_registry

            def task():
                reg = obs_registry.Registry(enabled=True)
                reg.counter("feed_rows_total", help="x").inc()
            """
        )}, docs={"docs/architecture.md": "| `feed_rows_total` | counter | x |"})
        self.assertEqual(len(findings), 1)
        self.assertIn("never merged", findings[0].message)

    def test_merged_private_registry_is_clean(self):
        findings = run_project_rule("metrics-contract", {LIB_PATH: _src(
            """
            from tensorflowonspark_tpu.obs import aggregate as obs_aggregate
            from tensorflowonspark_tpu.obs import registry as obs_registry

            def task(mgr):
                reg = obs_registry.Registry(enabled=True)
                reg.counter("feed_rows_total", help="x").inc()
                obs_aggregate.accumulate_to_channel(mgr, reg)
            """
        )}, docs={"docs/architecture.md": "| `feed_rows_total` | counter | x |"})
        self.assertEqual(findings, [])

    def test_dynamic_family_row_matches_minted_names(self):
        findings = run_project_rule("metrics-contract", {LIB_PATH: _src(
            """
            from tensorflowonspark_tpu import obs

            def work():
                obs.counter("chaos_fault_feed_stall_total", help="x").inc()
            """
        )}, docs={"docs/architecture.md": "| `chaos_fault_{site}_total` | counter | x |"})
        self.assertEqual(findings, [])


# ---------------------------------------------------------------------------
# lock-order
# ---------------------------------------------------------------------------

TWO_LOCK_CYCLE = _src(
    """
    import threading

    _lock_a = threading.Lock()
    _lock_b = threading.Lock()

    def forward():
        with _lock_a:
            with _lock_b:
                pass

    def backward():
        with _lock_b:
            with _lock_a:
                pass
    """
)


class TestLockOrder(unittest.TestCase):
    def test_two_lock_cycle_fires(self):
        findings = run_project_rule("lock-order", {LIB_PATH: TWO_LOCK_CYCLE})
        self.assertEqual(len(findings), 1)
        self.assertIn("cycle", findings[0].message)
        self.assertIn("_lock_a", findings[0].message)
        self.assertIn("_lock_b", findings[0].message)

    def test_consistent_order_is_clean(self):
        findings = run_project_rule("lock-order", {LIB_PATH: _src(
            """
            import threading

            _lock_a = threading.Lock()
            _lock_b = threading.Lock()

            def forward():
                with _lock_a:
                    with _lock_b:
                        pass

            def also_forward():
                with _lock_a:
                    with _lock_b:
                        pass
            """
        )})
        self.assertEqual(findings, [])

    def test_cross_module_cycle_through_calls_fires(self):
        findings = run_project_rule("lock-order", {
            "tensorflowonspark_tpu/mod_a.py": _src(
                """
                import threading

                from tensorflowonspark_tpu import mod_b

                _lock = threading.Lock()

                def locked_work():
                    with _lock:
                        mod_b.helper()

                def helper():
                    with _lock:
                        pass
                """
            ),
            "tensorflowonspark_tpu/mod_b.py": _src(
                """
                import threading

                from tensorflowonspark_tpu import mod_a

                _lock = threading.Lock()

                def helper():
                    with _lock:
                        pass

                def locked_work():
                    with _lock:
                        mod_a.helper()
                """
            ),
        })
        self.assertEqual(len(findings), 1)
        self.assertIn("cycle", findings[0].message)

    def test_blocking_put_on_bounded_queue_under_consumer_lock_fires(self):
        findings = run_project_rule("lock-order", {LIB_PATH: _src(
            """
            import queue
            import threading

            class Worker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._q = queue.Queue(2)
                    self._thread = threading.Thread(target=self._drain)
                    self._thread.start()

                def _drain(self):
                    while True:
                        item = self._q.get()
                        with self._lock:
                            del item

                def submit(self, item):
                    with self._lock:
                        self._q.put(item)
            """
        )})
        self.assertEqual(len(findings), 1)
        self.assertIn("bounded queue", findings[0].message)

    def test_put_with_timeout_or_unbounded_queue_is_clean(self):
        for variant in ("queue.Queue()", "queue.Queue(2)"):
            put = "self._q.put(item)" if variant == "queue.Queue()" else "self._q.put(item, timeout=1.0)"
            findings = run_project_rule("lock-order", {LIB_PATH: _src(
                """
                import queue
                import threading

                class Worker:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self._q = {}
                        self._thread = threading.Thread(target=self._drain)
                        self._thread.start()

                    def _drain(self):
                        while True:
                            item = self._q.get()
                            with self._lock:
                                del item

                    def submit(self, item):
                        with self._lock:
                            {}
                """.format(variant, put)
            )})
            self.assertEqual(findings, [], variant)

    def test_join_under_consumer_lock_fires_and_timeout_is_clean(self):
        template = _src(
            """
            import threading

            class Worker:
                def __init__(self):
                    self._lock = threading.Lock()
                    self._thread = threading.Thread(target=self._run)
                    self._thread.start()

                def _run(self):
                    with self._lock:
                        pass

                def close(self):
                    with self._lock:
                        self._thread.join({})
            """
        )
        findings = run_project_rule("lock-order", {LIB_PATH: template.format("")})
        self.assertEqual(len(findings), 1)
        self.assertIn("join()", findings[0].message)
        findings = run_project_rule(
            "lock-order", {LIB_PATH: template.format("timeout=5.0")}
        )
        self.assertEqual(findings, [])


# ---------------------------------------------------------------------------
# cross-rule interaction: suppressions + baselines for project findings
# ---------------------------------------------------------------------------


class TestProjectFindingFilters(unittest.TestCase):
    def test_block_scoped_suppression_on_for_header(self):
        # suppression on the for header covers the pooling line inside it
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax
            import numpy as np

            class Pool:
                def __init__(self):
                    self._slots = []

                def keep(self, leaves):
                    for leaf in leaves:  # tosa: disable=donation-safety -- zero-copy pool is intentional here
                        arr = np.asarray(jax.device_get(leaf))
                        self._slots.append(arr)
            """
        )}, keep_suppressed=True)
        self.assertEqual(len(findings), 1)
        self.assertIsNotNone(findings[0].suppressed)
        self.assertIn("zero-copy pool", findings[0].suppressed)

    def test_line_exact_suppression_still_works(self):
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax
            import numpy as np

            class Pool:
                def __init__(self):
                    self._slots = []

                def keep(self, leaf):
                    arr = np.asarray(jax.device_get(leaf))
                    self._slots.append(arr)  # tosa: disable=donation-safety -- fixture
            """
        )})
        self.assertEqual(findings, [])

    def test_suppression_of_other_rule_does_not_silence(self):
        findings = run_project_rule("donation-safety", {LIB_PATH: _src(
            """
            import jax
            import numpy as np

            class Pool:
                def __init__(self):
                    self._slots = []

                def keep(self, leaf):
                    arr = np.asarray(jax.device_get(leaf))
                    self._slots.append(arr)  # tosa: disable=lock-order -- wrong rule
            """
        )})
        self.assertEqual(len(findings), 1)

    def test_baseline_fingerprint_grandfathers_project_finding(self):
        findings = run_project_rule("lock-order", {LIB_PATH: TWO_LOCK_CYCLE})
        self.assertEqual(len(findings), 1)
        baseline = {findings[0].fingerprint: 1}
        # a fresh run of the same fixture produces the same fingerprint:
        # line-free, so unrelated edits elsewhere don't churn it
        again = run_project_rule("lock-order", {LIB_PATH: TWO_LOCK_CYCLE})
        core.apply_baseline(again, baseline)
        self.assertTrue(again[0].baselined)
        self.assertEqual(core.gating(again), [])

    def test_docs_anchored_finding_is_baselinable(self):
        files = {LIB_PATH: "def work():\n    pass\n"}
        docs = GOOD_DOCS  # documents good_things_total, never registered
        findings = run_project_rule("metrics-contract", files, docs=docs)
        self.assertEqual(len(findings), 1)
        self.assertEqual(findings[0].path, "docs/architecture.md")
        baseline = {findings[0].fingerprint: 1}
        again = run_project_rule("metrics-contract", files, docs=docs)
        core.apply_baseline(again, baseline)
        self.assertEqual(core.gating(again), [])


if __name__ == "__main__":
    unittest.main()
