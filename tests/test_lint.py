"""Lint-plane drills (ISSUE 9, tools/apexlint.py).

Mirrors the RetraceDetector drill style of tests/test_perf.py: every
rule gets a FIRE drill (a seeded violation must be caught at the
expected place) and a SILENT drill (the production-shaped idiom the
real code uses must not be flagged) — a rule that cannot pass both is
either blind or noisy.  On top of the per-rule pairs, the dogfood run
lints the real package + tools in tier-1 and must come back with ZERO
unbaselined findings and zero stale baseline entries, without importing
jax (the tool is pure stdlib ``ast``).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tools import apexlint

REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.abspath(apexlint.__file__)))


def lint(tmp_path, sources, rules=None, baseline=None):
    """Write fixture modules under tmp_path and lint them."""
    for rel, src in sources.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    return apexlint.run(sorted(sources), root=str(tmp_path),
                        rules=set(rules) if rules else None,
                        baseline=baseline)


def rules_of(report):
    return [f.rule for f in report.findings]


# ---------------------------------------------------------------------------
# donation-after-use
# ---------------------------------------------------------------------------

class TestDonationAfterUse:
    def test_fires_on_read_after_donating_dispatch(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(state):
                step = jax.jit(lambda s: s, donate_argnums=(0,))
                new = step(state)
                return state.sum()
        """}, rules=["donation-after-use"])
        assert rules_of(r) == ["donation-after-use"]
        assert "'state'" in r.findings[0].message
        assert r.findings[0].line == 7  # the read, not the dispatch

    def test_fires_across_loop_iterations(self, tmp_path):
        # the use sits lexically BEFORE the donating call but runs
        # after it on iteration 2 — the classic fused-scan bug shape
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(state):
                step = jax.jit(lambda s: s, donate_argnums=(0,))
                for _ in range(4):
                    print(state.shape)
                    out = step(state)
                return out
        """}, rules=["donation-after-use"])
        assert "donation-after-use" in rules_of(r)

    def test_silent_on_exclusive_else_branch(self, tmp_path):
        # the else-branch of the donating call's if can never observe
        # the donation — flow forks at the branch
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(state, cond):
                step = jax.jit(lambda s: s, donate_argnums=(0,))
                if cond:
                    new = step(state)
                else:
                    new = state.sum()
                return new
        """}, rules=["donation-after-use"])
        assert r.findings == []

    def test_fires_after_conditional_donation(self, tmp_path):
        # but AFTER the if, either branch may have donated: a read of
        # the buffer on the joined path is still a hazard
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(state, cond):
                step = jax.jit(lambda s: s, donate_argnums=(0,))
                if cond:
                    out = step(state)
                return state.sum()
        """}, rules=["donation-after-use"])
        assert "donation-after-use" in rules_of(r)

    def test_silent_on_nested_def_shadowed_local(self, tmp_path):
        # a nested def whose LOCAL happens to share the donated
        # buffer's name is not a read of the buffer
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(state):
                step = jax.jit(lambda s: s, donate_argnums=(0,))
                new = step(state)

                def helper():
                    state = [1, 2]
                    return state[0]

                return new, helper
        """}, rules=["donation-after-use"])
        assert r.findings == []

    def test_fires_on_closure_read_of_donated_buffer(self, tmp_path):
        # a genuinely free closure read of the donated buffer IS a
        # hazard (the closure may run after the dispatch)
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(state):
                step = jax.jit(lambda s: s, donate_argnums=(0,))
                new = step(state)

                def helper():
                    return state.sum()

                return new, helper
        """}, rules=["donation-after-use"])
        assert "donation-after-use" in rules_of(r)

    def test_silent_on_rebind_idiom(self, tmp_path):
        # the production idiom everywhere in agents/learner + actor:
        # the donated carry is rebound from the dispatch result
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(state, params):
                step = jax.jit(lambda s, p: (s, p), donate_argnums=(0,))
                for _ in range(4):
                    state, aux = step(state, params)
                    print(params)  # params is NOT donated
                return state
        """}, rules=["donation-after-use"])
        assert r.findings == []

    def test_self_attr_jit_registry(self, tmp_path):
        # feed_fn bound on self in __init__, dispatched in a method —
        # the memory/device_replay.py shape
        r = lint(tmp_path, {"m.py": """
            import jax

            class Ring:
                def __init__(self):
                    self._feed = jax.jit(lambda s, c: s, donate_argnums=0)

                def bad(self, state, chunk):
                    out = self._feed(state, chunk)
                    return state.fill

                def good(self, state, chunk):
                    state = self._feed(state, chunk)
                    return state.fill
        """}, rules=["donation-after-use"])
        assert rules_of(r) == ["donation-after-use"]
        assert r.findings[0].context.endswith("bad")


# ---------------------------------------------------------------------------
# rng-key-reuse
# ---------------------------------------------------------------------------

class TestRngKeyReuse:
    def test_fires_on_double_consumption(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(key):
                a = jax.random.uniform(key, (3,))
                b = jax.random.normal(key, (3,))
                return a + b
        """}, rules=["rng-key-reuse"])
        assert rules_of(r) == ["rng-key-reuse"]
        assert "consumed" in r.findings[0].message

    def test_fires_on_use_after_split(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(key):
                k1, k2 = jax.random.split(key)
                return jax.random.uniform(key, (3,))
        """}, rules=["rng-key-reuse"])
        assert rules_of(r) == ["rng-key-reuse"]

    def test_silent_on_split_per_consumer_and_fold_contract(self, tmp_path):
        # the tick_keys contract: the base key is re-folded forever and
        # never consumed directly; split outputs feed one draw each
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(base_key):
                k1, k2 = jax.random.split(base_key)
                a = jax.random.uniform(k1, (3,))
                b = jax.random.normal(k2, (3,))
                for t in range(4):
                    kt = jax.random.fold_in(base_key, t)
                    a = a + jax.random.uniform(kt, (3,))
                return a + b
        """}, rules=["rng-key-reuse"])
        assert r.findings == []

    def test_silent_on_loop_rebind(self, tmp_path):
        # agents/learner.py:~591 — split amortized over a buffer, the
        # operand rebound from the split's own output
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(device_key):
                buf = []
                while True:
                    keys = jax.random.split(device_key, 65)
                    device_key = keys[0]
                    buf = list(keys[1:])
        """}, rules=["rng-key-reuse"])
        assert r.findings == []

    def test_literal_seed_fires_outside_rngs(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            import jax

            def f():
                return jax.random.PRNGKey(42)
        """}, rules=["rng-key-reuse"])
        assert rules_of(r) == ["rng-key-reuse"]
        assert "literal seed" in r.findings[0].message

    def test_silent_on_exclusive_branch_consumers(self, tmp_path):
        # only one branch ever executes: consuming the same key in
        # mutually exclusive if/else arms is not reuse
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(key, flag):
                if flag:
                    x = jax.random.uniform(key, (3,))
                else:
                    x = jax.random.normal(key, (3,))
                return x
        """}, rules=["rng-key-reuse"])
        assert r.findings == []

    def test_fires_on_consumption_after_branch_consumption(self, tmp_path):
        # but after the join, a branch may have consumed the key — a
        # further draw is reuse on that path
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(key, flag):
                if flag:
                    x = jax.random.uniform(key, (3,))
                return jax.random.normal(key, (3,))
        """}, rules=["rng-key-reuse"])
        assert "rng-key-reuse" in rules_of(r)

    def test_literal_seed_silent_in_rngs_and_for_derived(self, tmp_path):
        r = lint(tmp_path, {
            "utils/rngs.py": """
                import jax

                def root(root_seed):
                    return jax.random.PRNGKey(0)
            """,
            "m.py": """
                import jax

                def f(seed):
                    return jax.random.PRNGKey(seed)
            """}, rules=["rng-key-reuse"])
        assert r.findings == []


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------

class TestRetraceHazard:
    def test_fires_on_loop_counter_into_jit(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(params):
                step = jax.jit(lambda p, t: p)
                for i in range(100):
                    step(params, i)
        """}, rules=["retrace-hazard"])
        assert rules_of(r) == ["retrace-hazard"]
        assert "'i'" in r.findings[0].message

    def test_fires_on_bumped_host_counter(self, tmp_path):
        # the weak-typed tick leak the runtime RetraceDetector drill
        # seeds (tests/test_perf.py): a python int bumped per dispatch
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(params, clock):
                step = jax.jit(lambda p, t: p)
                tick = 0
                while clock.running():
                    step(params, tick)
                    tick += 8
        """}, rules=["retrace-hazard"])
        assert rules_of(r) == ["retrace-hazard"]

    def test_silent_on_device_resident_tick(self, tmp_path):
        # agents/actor.py device loop idiom: tick0 = jnp.int32(0),
        # advanced arithmetically — stays a traced array, never retraces
        r = lint(tmp_path, {"m.py": """
            import jax
            import jax.numpy as jnp

            def f(params, clock):
                step = jax.jit(lambda p, t: p)
                tick0 = jnp.int32(0)
                while clock.running():
                    out = step(params, tick0)
                    tick0 = tick0 + 8
                return out
        """}, rules=["retrace-hazard"])
        assert r.findings == []

    def test_fires_on_unhashable_static_arg(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(x):
                g = jax.jit(lambda a, shape: a, static_argnums=(1,))
                for _ in range(2):
                    g(x, [84, 84])
        """}, rules=["retrace-hazard"])
        assert rules_of(r) == ["retrace-hazard"]
        assert "unhashable" in r.findings[0].message

    def test_silent_on_hashable_static_arg(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            import jax

            def f(x):
                g = jax.jit(lambda a, shape: a, static_argnums=(1,))
                for _ in range(2):
                    g(x, (84, 84))
        """}, rules=["retrace-hazard"])
        assert r.findings == []


# ---------------------------------------------------------------------------
# single-owner
# ---------------------------------------------------------------------------

_OWNER_SRC = """
    class RingOwner:
        __apex_mutators__ = ("drain",)
        __apex_owner__ = ("agents.learner",)

        def drain(self):
            return 0

        def pump(self):
            return self.drain()  # defining module: always allowed
"""


class TestSingleOwner:
    def test_fires_outside_owner_set(self, tmp_path):
        r = lint(tmp_path, {
            "pkg/owner.py": _OWNER_SRC,
            "pkg/rogue.py": """
                from pkg.owner import RingOwner

                def f():
                    o = RingOwner()
                    return o.drain()
            """}, rules=["single-owner"])
        assert rules_of(r) == ["single-owner"]
        assert r.findings[0].path == "pkg/rogue.py"

    def test_silent_in_owner_module_and_defining_module(self, tmp_path):
        r = lint(tmp_path, {
            "pkg/owner.py": _OWNER_SRC,
            "pkg/agents/learner.py": """
                from pkg.owner import RingOwner

                def f():
                    o = RingOwner()
                    return o.drain()
            """}, rules=["single-owner"])
        assert r.findings == []

    def test_factory_receiver_resolution(self, tmp_path):
        # health.get_quarantine(...).put(...) — chained factory call
        r = lint(tmp_path, {
            "pkg/health.py": """
                __apex_factories__ = {"get_store": "Store"}

                class Store:
                    __apex_mutators__ = ("put",)
                    __apex_owner__ = ("memory.",)

                    def put(self, items):
                        pass

                def get_store(name):
                    return Store()
            """,
            "pkg/stray.py": """
                from pkg.health import get_store

                def f(items):
                    get_store("x").put(items)
            """,
            "pkg/memory/feeder.py": """
                from pkg.health import get_store

                def f(items):
                    get_store("x").put(items)
            """}, rules=["single-owner"])
        assert rules_of(r) == ["single-owner"]
        assert r.findings[0].path == "pkg/stray.py"

    def test_module_fn_owners(self, tmp_path):
        r = lint(tmp_path, {
            "pkg/ring.py": """
                __apex_fn_owners__ = {"ring_write": ("memory.",)}

                def ring_write(state):
                    return state
            """,
            "pkg/stray.py": """
                from pkg.ring import ring_write

                def f(state):
                    return ring_write(state)
            """,
            "pkg/memory/per.py": """
                from pkg.ring import ring_write

                def f(state):
                    return ring_write(state)
            """}, rules=["single-owner"])
        assert rules_of(r) == ["single-owner"]
        assert r.findings[0].path == "pkg/stray.py"

    def test_real_annotations_are_discovered(self):
        """The production classes declare the ownership registry the
        rule is driven by (QueueOwner/ingests/quarantine + ring fns)."""
        from pytorch_distributed_tpu.memory.feeder import QueueOwner
        from pytorch_distributed_tpu.memory.device_replay import (
            DeviceReplayIngest,
        )
        from pytorch_distributed_tpu.utils.health import QuarantineStore

        assert "drain" in QueueOwner.__apex_mutators__
        assert any("learner" in o for o in QueueOwner.__apex_owner__)
        assert "drain" in DeviceReplayIngest.__apex_mutators__
        assert "put" in QuarantineStore.__apex_mutators__


# ---------------------------------------------------------------------------
# schema-contract
# ---------------------------------------------------------------------------

class TestSchemaContract:
    def test_fires_on_positional_index(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            def f(rows):
                t = Transition(1, 2, 3, 4, 5, 6)
                return t[0]
        """}, rules=["schema-contract"])
        assert rules_of(r) == ["schema-contract"]
        assert ".state0" in r.findings[0].hint

    def test_silent_on_named_fields(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            def f(rows):
                t = Transition(1, 2, 3, 4, 5, 6)
                return t.state0, t.gamma_n
        """}, rules=["schema-contract"])
        assert r.findings == []

    def test_fires_on_shadow_schema_tuple(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            FIELDS = ("state0", "action", "reward", "gamma_n")
        """}, rules=["schema-contract"])
        assert rules_of(r) == ["schema-contract"]
        assert "re-typed" in r.findings[0].message

    def test_silent_on_short_field_subsets(self, tmp_path):
        # utils/health.py-style scalar-column lists are fine
        r = lint(tmp_path, {"m.py": """
            SCALARS = ("reward", "gamma_n", "terminal1")
        """}, rules=["schema-contract"])
        assert r.findings == []

    def test_fires_on_transition_fields_attr(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            def f():
                return list(Transition._fields)
        """}, rules=["schema-contract"])
        assert rules_of(r) == ["schema-contract"]
        assert "REPLAY_FIELDS" in r.findings[0].hint

    def test_wire_columns_drift_fires(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            from schema import REPLAY_FIELDS

            WIRE_COLUMNS = REPLAY_FIELDS + ("priority",)

            def encode_chunk(items):
                cols = {}
                cols["priority"] = 1.0
                cols["bogus"] = 2.0
                return cols
        """}, rules=["schema-contract"])
        assert rules_of(r) == ["schema-contract"]
        assert "'bogus'" in r.findings[0].message

    def test_wire_columns_declared_stays_silent(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            from schema import REPLAY_FIELDS

            WIRE_COLUMNS = REPLAY_FIELDS + ("priority", "trace_id")

            def decode_chunk(cols):
                return cols["state0"], cols.get("trace_id")
        """}, rules=["schema-contract"])
        assert r.findings == []


# ---------------------------------------------------------------------------
# knob-registry
# ---------------------------------------------------------------------------

_KNOB_DOCS = {
    "README.md": "knobs: TPU_APEX_DEMO and TPU_APEX_FAM_ families\n",
    "TESTING.md": "drill knobs: TPU_APEX_DEMO, TPU_APEX_FAM_*\n",
}


def _write_docs(tmp_path, docs=_KNOB_DOCS):
    for name, text in docs.items():
        (tmp_path / name).write_text(text)


class TestKnobRegistry:
    def test_undeclared_read_fires(self, tmp_path):
        _write_docs(tmp_path)
        r = lint(tmp_path, {
            "config.py": 'KNOBS = (("TPU_APEX_DEMO", "m.py", "demo"),)\n',
            "m.py": """
                import os

                def f():
                    return os.environ.get("TPU_APEX_BOGUS")
            """}, rules=["knob-registry"])
        assert any("TPU_APEX_BOGUS" in f.message for f in r.findings)

    def test_declared_documented_read_is_silent(self, tmp_path):
        _write_docs(tmp_path)
        r = lint(tmp_path, {
            "config.py": 'KNOBS = (("TPU_APEX_DEMO", "m.py", "demo"),)\n',
            "m.py": """
                import os

                def f():
                    return os.environ.get("TPU_APEX_DEMO")
            """}, rules=["knob-registry"])
        assert r.findings == []

    def test_family_prefix_constant_resolves(self, tmp_path):
        # the utils/health.py resolve() idiom: _ENV_PREFIX + field
        _write_docs(tmp_path)
        r = lint(tmp_path, {
            "config.py":
                'KNOBS = (("TPU_APEX_FAM_*", "m.py", "family"),)\n',
            "m.py": """
                import os

                _ENV_PREFIX = "TPU_APEX_FAM_"

                def resolve(field):
                    return os.environ.get(_ENV_PREFIX + field.upper())
            """}, rules=["knob-registry"])
        assert r.findings == []

    def test_declared_but_never_read_fires(self, tmp_path):
        _write_docs(tmp_path, {
            "README.md": "TPU_APEX_DEMO TPU_APEX_DEAD\n",
            "TESTING.md": "TPU_APEX_DEMO TPU_APEX_DEAD\n"})
        r = lint(tmp_path, {
            "config.py": ('KNOBS = (("TPU_APEX_DEMO", "m.py", "demo"),\n'
                          '         ("TPU_APEX_DEAD", "m.py", "dead"),)\n'),
            "m.py": """
                import os

                def f():
                    return os.environ.get("TPU_APEX_DEMO")
            """}, rules=["knob-registry"])
        assert any("never read" in f.message for f in r.findings)

    def test_undocumented_knob_fires_per_doc(self, tmp_path):
        _write_docs(tmp_path, {"README.md": "TPU_APEX_DEMO\n",
                               "TESTING.md": "nothing here\n"})
        r = lint(tmp_path, {
            "config.py": 'KNOBS = (("TPU_APEX_DEMO", "m.py", "demo"),)\n',
            "m.py": """
                import os

                def f():
                    return os.environ.get("TPU_APEX_DEMO")
            """}, rules=["knob-registry"])
        assert any("TESTING.md" in f.message for f in r.findings)
        assert not any("README.md" in f.message for f in r.findings)

    def test_param_propagation_through_env_helper(self, tmp_path):
        # utils/tracing.py shape: the read happens inside _env_flag and
        # the knob name arrives from its call sites
        _write_docs(tmp_path)
        r = lint(tmp_path, {
            "config.py": 'KNOBS = (("TPU_APEX_DEMO", "m.py", "demo"),)\n',
            "m.py": """
                import os

                def _env_flag(name, default):
                    raw = os.environ.get(name)
                    return default if raw is None else raw == "1"

                def active():
                    return _env_flag("TPU_APEX_DEMO", True)
            """}, rules=["knob-registry"])
        assert r.findings == []


# ---------------------------------------------------------------------------
# generic pass
# ---------------------------------------------------------------------------

class TestGenericPass:
    def test_unused_import_fires(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            import os
            import sys

            def f():
                return sys.platform
        """}, rules=["unused-import"])
        assert rules_of(r) == ["unused-import"]
        assert "'os'" in r.findings[0].message

    def test_unused_import_exemptions(self, tmp_path):
        # __init__ re-export surface, explicit as-reexport, __all__
        r = lint(tmp_path, {
            "pkg/__init__.py": "import os\n",
            "m.py": """
                import os as os
                import sys

                __all__ = ("sys",)
            """}, rules=["unused-import"])
        assert r.findings == []

    def test_undefined_name_fires(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            def f():
                return bogus_name + 1
        """}, rules=["undefined-name"])
        assert rules_of(r) == ["undefined-name"]

    def test_undefined_silent_on_nested_comprehension_scopes(self, tmp_path):
        # the memory/device_replay.py idiom that defeats naive scopers:
        # a comprehension inside a genexp inside a call, plus lambdas
        r = lint(tmp_path, {"m.py": """
            def f(rows, fields, g):
                out = g(*(
                    [g(r, f) for r in rows]
                    for f in fields))
                h = sorted(fields, key=lambda p: -sum(
                    len(p) for _ in rows))
                return out, h
        """}, rules=["undefined-name"])
        assert r.findings == []

    def test_shadowed_builtin_fires_and_pragma_silences(self, tmp_path):
        r = lint(tmp_path, {"m.py": """
            def f(list):
                dict = 1  # apexlint: ignore[shadowed-builtin]
                return list, dict
        """}, rules=["shadowed-builtin"])
        assert rules_of(r) == ["shadowed-builtin"]
        assert r.findings[0].message.startswith("'list'")

    def test_parse_error_is_a_finding(self, tmp_path):
        r = lint(tmp_path, {"m.py": "def f(:\n"})
        assert rules_of(r) == ["parse-error"]

    def test_null_byte_source_is_a_finding_not_a_crash(self, tmp_path):
        (tmp_path / "nul.py").write_bytes(b"X = 1\x00\n")
        r = apexlint.run(["nul.py"], root=str(tmp_path))
        assert rules_of(r) == ["parse-error"]


# ---------------------------------------------------------------------------
# baseline workflow + CLI
# ---------------------------------------------------------------------------

class TestBaselineAndCli:
    def _finding_fixture(self, tmp_path):
        (tmp_path / "m.py").write_text(
            "import jax\n\n\ndef f():\n"
            "    return jax.random.PRNGKey(7)\n")

    def test_baseline_suppresses_and_detects_stale(self, tmp_path):
        self._finding_fixture(tmp_path)
        rep = apexlint.run(["m.py"], root=str(tmp_path),
                           rules={"rng-key-reuse"})
        assert len(rep.findings) == 1
        f = rep.findings[0]
        base = tmp_path / "base.json"
        base.write_text(json.dumps({"entries": [
            {"rule": f.rule, "path": f.path, "context": f.context,
             "message": f.message, "justification": "drill fixture"},
            # in-scope (same scanned file + rule) but matching nothing:
            # must surface as stale so the baseline gets pruned
            {"rule": "rng-key-reuse", "path": f.path, "context": "gone",
             "message": "no longer exists",
             "justification": "stale on purpose"},
        ]}))
        rep2 = apexlint.run(["m.py"], root=str(tmp_path),
                            rules={"rng-key-reuse"},
                            baseline=str(base))
        assert rep2.findings == [] and rep2.suppressed == 1
        assert len(rep2.stale) == 1 and not rep2.clean

    def test_empty_justification_is_an_error(self, tmp_path):
        base = tmp_path / "base.json"
        base.write_text(json.dumps({"entries": [
            {"rule": "x", "path": "m.py", "context": "", "message": "m",
             "justification": "  "}]}))
        with pytest.raises(apexlint.BaselineError):
            apexlint.load_baseline(str(base))
        base.write_text(json.dumps({"entries": [
            {"rule": "x", "path": "m.py", "context": "", "message": "m",
             "justification": "TODO: justify or fix"}]}))
        with pytest.raises(apexlint.BaselineError):
            apexlint.load_baseline(str(base))

    def test_cli_exit_codes_and_json(self, tmp_path):
        self._finding_fixture(tmp_path)
        rc = apexlint.main(["m.py", "--root", str(tmp_path),
                            "--rules", "rng-key-reuse", "--json"])
        assert rc == 1
        (tmp_path / "clean.py").write_text("X = 1\n")
        rc = apexlint.main(["clean.py", "--root", str(tmp_path),
                            "--json"])
        assert rc == 0
        assert apexlint.main(["--rules", "not-a-rule"]) == 2

    def test_subset_runs_carry_out_of_scope_entries(self, tmp_path):
        """A --rules/--paths subset invocation must neither fail on
        baseline entries it could never match nor destroy them."""
        self._finding_fixture(tmp_path)
        (tmp_path / "clean.py").write_text("X = 1\n")
        base = tmp_path / "base.json"
        rep = apexlint.run(["m.py"], root=str(tmp_path),
                           rules={"rng-key-reuse"})
        f = rep.findings[0]
        base.write_text(json.dumps({"entries": [
            {"rule": f.rule, "path": f.path, "context": f.context,
             "message": f.message, "justification": "drill fixture"}]}))
        # rule subset that excludes rng-key-reuse: entry is carried,
        # not stale — the run stays clean
        rep2 = apexlint.run(["m.py"], root=str(tmp_path),
                            rules={"unused-import"},
                            baseline=str(base))
        assert rep2.clean and rep2.stale == []
        assert len(rep2.carried_entries) == 1
        # path subset that excludes m.py: same carry semantics
        rep3 = apexlint.run(["clean.py"], root=str(tmp_path),
                            baseline=str(base))
        assert rep3.clean and len(rep3.carried_entries) == 1

    def test_one_entry_suppresses_exactly_one_finding(self, tmp_path):
        """Two identical violations + one justified entry: the second
        must surface as a finding, not ride the first's
        justification."""
        (tmp_path / "m.py").write_text(
            "import jax\n\n\ndef f():\n"
            "    a = jax.random.PRNGKey(7)\n"
            "    b = jax.random.PRNGKey(7)\n"
            "    return a, b\n")
        rep = apexlint.run(["m.py"], root=str(tmp_path),
                           rules={"rng-key-reuse"})
        assert len(rep.findings) == 2
        f = rep.findings[0]
        base = tmp_path / "base.json"
        base.write_text(json.dumps({"entries": [
            {"rule": f.rule, "path": f.path, "context": f.context,
             "message": f.message, "justification": "only one"}]}))
        rep2 = apexlint.run(["m.py"], root=str(tmp_path),
                            rules={"rng-key-reuse"},
                            baseline=str(base))
        assert rep2.suppressed == 1 and len(rep2.findings) == 1

    def test_deleted_file_entries_go_stale_on_dir_runs(self, tmp_path):
        """An entry for a file deleted from a scanned directory must be
        reported stale (the baseline shrinks), not carried forever."""
        pkg = tmp_path / "pkg"
        pkg.mkdir()
        (pkg / "live.py").write_text("X = 1\n")
        base = tmp_path / "base.json"
        base.write_text(json.dumps({"entries": [
            {"rule": "unused-import", "path": "pkg/gone.py",
             "context": "", "message": "'os' is imported but never "
             "used", "justification": "file was deleted"}]}))
        rep = apexlint.run(["pkg"], root=str(tmp_path),
                           baseline=str(base))
        assert len(rep.stale) == 1 and not rep.clean
        assert rep.carried_entries == []

    def test_write_baseline_preserves_justified_entries(self, tmp_path):
        """Regenerating the baseline must keep matched entries'
        written justifications and only skeleton NEW findings."""
        self._finding_fixture(tmp_path)
        (tmp_path / "n.py").write_text(
            "import jax\n\n\ndef g():\n"
            "    return jax.random.PRNGKey(9)\n")
        base = tmp_path / "base.json"
        rep = apexlint.run(["m.py"], root=str(tmp_path),
                           rules={"rng-key-reuse"})
        f = rep.findings[0]
        base.write_text(json.dumps({"entries": [
            {"rule": f.rule, "path": f.path, "context": f.context,
             "message": f.message, "justification": "keep me"}]}))
        out = tmp_path / "regen.json"
        rc = apexlint.main(["m.py", "n.py", "--root", str(tmp_path),
                            "--rules", "rng-key-reuse",
                            "--baseline", str(base),
                            "--write-baseline", str(out)])
        assert rc == 1  # the n.py finding is new
        entries = json.loads(out.read_text())["entries"]
        justs = {e["path"]: e["justification"] for e in entries}
        assert justs["m.py"] == "keep me"
        assert "TODO" in justs["n.py"]

    def test_wildcard_read_does_not_mask_dead_knob_check(self, tmp_path):
        """An opaque dynamic env read ('*' pattern) must not cover
        declared-but-never-read knobs."""
        (tmp_path / "README.md").write_text("TPU_APEX_DEAD\n")
        (tmp_path / "TESTING.md").write_text("TPU_APEX_DEAD\n")
        r = lint(tmp_path, {
            "config.py":
                'KNOBS = (("TPU_APEX_DEAD", "m.py", "dead"),)\n',
            "m.py": """
                import os

                def f(role):
                    return os.environ.get(role.upper())
            """}, rules=["knob-registry"])
        assert any("never read" in f.message for f in r.findings)

    def test_write_baseline_skeleton_requires_justification(self, tmp_path):
        self._finding_fixture(tmp_path)
        out = tmp_path / "skel.json"
        rc = apexlint.main(["m.py", "--root", str(tmp_path),
                            "--rules", "rng-key-reuse",
                            "--write-baseline", str(out)])
        assert rc == 1  # findings existed
        with pytest.raises(apexlint.BaselineError):
            apexlint.load_baseline(str(out))  # TODO justifications

    def test_cli_subprocess_json_smoke(self, tmp_path):
        (tmp_path / "m.py").write_text("import os\n")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO_ROOT, "tools",
                                          "apexlint.py"),
             "m.py", "--root", str(tmp_path), "--json"],
            capture_output=True, text=True)
        assert proc.returncode == 1
        payload = json.loads(proc.stdout)
        assert payload["counts"] == {"unused-import": 1}


# ---------------------------------------------------------------------------
# the dogfood run: the real package must lint clean in tier-1
# ---------------------------------------------------------------------------

class TestDogfood:
    def test_package_and_tools_lint_clean(self):
        """ISSUE 9 acceptance: zero unbaselined findings, zero stale
        baseline entries, across ALL rules including the generic
        pass."""
        baseline = os.path.join(REPO_ROOT, "tools",
                                "apexlint_baseline.json")
        rep = apexlint.run(["pytorch_distributed_tpu", "tools"],
                           root=REPO_ROOT, baseline=baseline)
        msgs = [f.format() for f in rep.findings]
        assert rep.findings == [], "\n".join(msgs)
        assert rep.stale == [], rep.stale
        assert rep.files > 80  # the whole package actually scanned

    def test_no_jax_import(self):
        """The linter must stay usable on jax-less CI hosts (and fast:
        importing jax costs seconds on the 2-vCPU image)."""
        script = (
            "import sys, importlib.util\n"
            "class Blocker:\n"
            "    def find_module(self, name, path=None):\n"
            "        if name.split('.')[0] == 'jax':\n"
            "            raise ImportError('jax import blocked')\n"
            "sys.meta_path.insert(0, Blocker())\n"
            "spec = importlib.util.spec_from_file_location('apexlint', "
            f"{os.path.join(REPO_ROOT, 'tools', 'apexlint.py')!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "sys.modules['apexlint'] = m\n"
            "spec.loader.exec_module(m)\n"
            "assert m.main(['--list-rules']) == 0\n"
            "print('OK')\n")
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True)
        assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr

    def test_knob_registry_matches_reality(self):
        """config.KNOBS covers the knobs the repo actually documents as
        its surface (a canary beyond the mechanical rule)."""
        from pytorch_distributed_tpu.config import KNOBS

        names = {k[0] for k in KNOBS}
        for expected in ("TPU_APEX_PERF", "TPU_APEX_PERF_*",
                         "TPU_APEX_HEALTH_*", "TPU_APEX_QUARANTINE",
                         "*_FAULTS", "DCN_FAULTS_*"):
            assert expected in names
        # every row is (name, where, doc) with substance
        for name, where, doc in KNOBS:
            assert name and where.endswith(".py") and len(doc) > 8

    def test_check_sh_lint_stage(self):
        """The pre-PR gate's lint stage passes on the repo as checked
        in (the fencing drills skipped: they have their own tests)."""
        proc = subprocess.run(
            ["bash", os.path.join(REPO_ROOT, "tools", "check.sh")],
            env={**os.environ, "APEXLINT_ONLY": "1"},
            cwd=REPO_ROOT, capture_output=True, text=True)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "apexlint: PASS" in proc.stdout


# ---------------------------------------------------------------------------
# the gate script and the docs name only what the tree holds
# ---------------------------------------------------------------------------

_UNTRACKED_DIRS = {".git", "__pycache__", ".pytest_cache", ".hypothesis",
                   "logs", "models", "runs", "chiprun_out", ".jax_cache",
                   ".bench_run", ".bench_archive", "build"}


def _tracked_files():
    """Paths git would commit, relative to the repo root; where the
    checkout is no repository, what a walk finds outside the directories
    ``.gitignore`` lists."""
    if os.path.isdir(os.path.join(REPO_ROOT, ".git")):
        proc = subprocess.run(
            ["git", "ls-files", "--cached", "--others",
             "--exclude-standard"],
            cwd=REPO_ROOT, capture_output=True, text=True)
        if proc.returncode == 0:
            return [f for f in proc.stdout.splitlines()
                    if os.path.isfile(os.path.join(REPO_ROOT, f))]
    found = []
    for base, dirs, files in os.walk(REPO_ROOT):
        dirs[:] = [d for d in dirs if d not in _UNTRACKED_DIRS]
        found += [os.path.relpath(os.path.join(base, f), REPO_ROOT)
                  for f in files if not f.endswith((".pyc", ".log"))]
    return found


def test_check_sh_runs_only_what_exists():
    """``tools/check.sh`` parses, and every file it names is in the
    tree: a stage whose script was deleted fails here, not before a
    PR."""
    import re

    path = os.path.join(REPO_ROOT, "tools", "check.sh")
    proc = subprocess.run(["bash", "-n", path], capture_output=True,
                          text=True)
    assert proc.returncode == 0, proc.stderr
    text = re.sub(r"\$tmp/[\w.]+", "", open(path).read())
    named = set(re.findall(
        r"(?<![\w./-])([\w][\w./-]*\.(?:py|sh|json|jsonl|md))\b", text))
    assert {"tools/apexlint.py", "tools/fleet_top.py",
            "tools/chaos_soak.py"} <= named
    tracked = set(_tracked_files())
    assert sorted(n for n in named if n not in tracked) == []
    # stages 1, 1b, 1c, 1d and nothing after them
    assert re.findall(r"== stage (\w+):", text) == ["1", "1b", "1c", "1d"]


# retired with the pre-chip measurement stack and the NHWC ring fork (PR
# 28).  History may name them: CHANGES.md, ROADMAP.md, PERF.md from its
# Findings on, the issue that retired them and a reviewer's notes on a
# PR (REVIEW.md quotes file names); benchmark/ is not ours.
RETIRED = ["bench.py", "bench_gate", "mfu_probe", "BENCH_SMOKE_BASELINE",
           "device_channels_last", "nhwc_input"]
_HISTORY = {"CHANGES.md", "ROADMAP.md", "ISSUE.md", "REVIEW.md",
            "PERF_LEDGER.jsonl", os.path.join("tests", "test_lint.py")}


@pytest.mark.parametrize("name", RETIRED)
def test_no_tracked_text_names_a_retired_path(name):
    hits = []
    for rel in _tracked_files():
        if rel in _HISTORY or rel.split(os.sep)[0] == "benchmark":
            continue
        try:
            text = open(os.path.join(REPO_ROOT, rel),
                        encoding="utf-8").read()
        except (UnicodeDecodeError, OSError):
            continue  # not a text file
        if rel == "PERF.md":
            text = text.split("\n## 6. Findings")[0]
        hits += [f"{rel}:{i}" for i, line in
                 enumerate(text.splitlines(), 1) if name in line]
    assert hits == []
