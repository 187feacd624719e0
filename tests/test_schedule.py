"""Schedule-IR unit tests: validation, double-buffer (generation) semantics,
builder structure, and a device-free executor run.

Multi-device executor-vs-oracle equivalence (forward + gradients, 4 and 8
fake devices) lives in ``tests/test_strategies.py`` →
``repro.testing.strategy_check``; these tests pin the IR itself and run in
the fast tier with an injected ``shift_fn`` instead of real collectives.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.schedule import (
    Compute,
    Merge,
    Schedule,
    ScheduleError,
    Send,
    Step,
    execute_schedule,
)


def tag_shift(payload, axis_name, shift):
    """Fake ring shift: adds ``1000 * |shift|`` to every leaf, marking that
    the wire saw exactly the step-entry generation of the buffer."""
    return jax.tree.map(lambda x: x + 1000.0 * abs(shift), payload)


def _pair(out_val, lse_val, S=2):
    return (
        jnp.full((S, 1, 1), float(out_val), jnp.float32),
        jnp.full((S, 1), float(lse_val), jnp.float32),
    )


def _kv(val, S=2):
    x = jnp.full((1, S, 1, 1), float(val), jnp.float32)
    return (x, x, jnp.zeros((1, S), jnp.int32))


# ---------------------------------------------------------------------------
# validation


class TestValidation:
    def test_aliasing_send_and_compute_write(self):
        # A Send reception and a Compute output landing in one buffer in the
        # same step would make two generations alias.
        s = Schedule(prologue=(
            Step(Send(("p",), 1), Compute("q", ("kv",), "p")),
        ))
        with pytest.raises(ScheduleError, match="alias"):
            s.validate({"q", "kv", "p"})

    def test_aliasing_two_sends(self):
        s = Schedule(prologue=(
            Step(Send(("a",), 1, into=("x",)), Send(("b",), -1, into=("x",))),
        ))
        with pytest.raises(ScheduleError, match="alias"):
            s.validate({"a", "b"})

    def test_snapshot_read_while_written_is_legal(self):
        # The double buffer: sending a buffer's current generation while a
        # Compute writes its next one is the whole point — distinct names,
        # no alias.
        s = Schedule(prologue=(
            Step(Send(("p",), 1, into=("ph",)), Compute("q", ("kv",), "p")),
        ))
        s.validate({"q", "kv", "p"})

    def test_unknown_read(self):
        s = Schedule(prologue=(Step(Send(("nope",), 1)),))
        with pytest.raises(ScheduleError, match="unknown buffer"):
            s.validate({"q"})

    def test_merge_unknown_src(self):
        s = Schedule(prologue=(Step(Merge("acc", "nope")),))
        with pytest.raises(ScheduleError, match="unknown buffer"):
            s.validate({"acc"})

    def test_body_cannot_grow_carry(self):
        s = Schedule(
            body=Step(Send(("q",), 1, into=("fresh",))), trips=2,
        )
        with pytest.raises(ScheduleError, match="new buffer"):
            s.validate({"q"})

    def test_body_cannot_write_static(self):
        s = Schedule(
            body=Step(Send(("kv",), 1)), trips=2, static=frozenset({"kv"}),
        )
        with pytest.raises(ScheduleError, match="static"):
            s.validate({"kv"})

    def test_trips_without_body(self):
        with pytest.raises(ScheduleError, match="no body"):
            Schedule(trips=3).validate(set())

    def test_send_into_length_mismatch(self):
        s = Schedule(prologue=(Step(Send(("a", "b"), 1, into=("x",))),))
        with pytest.raises(ScheduleError, match="does not match"):
            s.validate({"a", "b"})


# ---------------------------------------------------------------------------
# generation (double-buffer) semantics, via an injected shift_fn


class TestGenerations:
    def _flash(self, out_val):
        def compute(q, qp, k, v, kp):
            del qp, k, v, kp
            return (
                jnp.full((q.shape[0], 1, 1), float(out_val), jnp.float32),
                jnp.zeros((q.shape[0], 1), jnp.float32),
            )

        return compute

    def test_send_reads_step_entry_generation(self):
        # Step: Send p -> ph while Compute overwrites p.  The wire must carry
        # p's *entry* value (2), not the freshly computed 5.
        bufs = {
            "q": (jnp.zeros((2, 1)), jnp.zeros((2,), jnp.int32)),
            "kv": _kv(0.0),
            "p": _pair(2.0, 0.0),
        }
        sched = Schedule(prologue=(
            Step(Send(("p",), 1, into=("ph",)), Compute("q", ("kv",), "p")),
        ))
        for overlap in (True, False):
            res = execute_schedule(
                sched, bufs, axis_name=None, compute_fn=self._flash(5.0),
                overlap=overlap, shift_fn=tag_shift,
            )
            np.testing.assert_allclose(np.asarray(res["ph"][0]), 1002.0)
            np.testing.assert_allclose(np.asarray(res["p"][0]), 5.0)

    def test_merge_sees_received_generation(self):
        # Step: rotate the accumulator AND merge this step's partial into it
        # — the TokenRing lag pattern.  The merge must fold into the
        # *received* accumulator (entry value + wire tag), not the entry one.
        bufs = {
            "q": (jnp.zeros((2, 1)), jnp.zeros((2,), jnp.int32)),
            "kv": _kv(0.0),
            "acc": _pair(7.0, 0.0),
        }
        sched = Schedule(prologue=(
            Step(
                Send(("acc",), 1),
                Compute("q", ("kv",), "p"),
                Merge("acc", "p"),
            ),
        ))
        res = execute_schedule(
            sched, bufs, axis_name=None, compute_fn=self._flash(3.0),
            overlap=True, shift_fn=tag_shift,
        )
        out, lse = res["acc"]
        # received acc has lse 1000 vs the partial's 0: the merge weight of
        # the partial is e^-1000 ~ 0, so out ~ the received 1007, and the
        # merged lse ~ 1000.  Entry-generation acc (lse 0) would give ~505.
        np.testing.assert_allclose(np.asarray(out)[0, 0, 0], 1007.0, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(lse)[0, 0], 1000.0, rtol=1e-6)

    def test_modes_produce_identical_values(self):
        bufs = {
            "q": (jnp.ones((2, 1)), jnp.zeros((2,), jnp.int32)),
            "kv": _kv(1.0),
            "acc": _pair(0.5, 0.25),
        }
        sched = Schedule(prologue=(
            Step(Send(("acc",), 1), Compute("q", ("kv",), "p"), Merge("acc", "p")),
        ))
        res = {
            ov: execute_schedule(
                sched, bufs, axis_name=None, compute_fn=self._flash(2.0),
                overlap=ov, shift_fn=tag_shift,
            )
            for ov in (True, False)
        }
        for name in res[True]:
            for a, b in zip(
                jax.tree.leaves(res[True][name]), jax.tree.leaves(res[False][name])
            ):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# builder structure: the migrated strategies' schedules at the IR level


def _send_hops(schedule, buffer):
    """(shift, count) totals for Sends of ``buffer`` over the unrolled steps."""
    hops = {}
    for step in schedule.all_steps():
        for op in step.sends:
            if buffer in op.buffers:
                hops[op.shift] = hops.get(op.shift, 0) + 1
    return hops


class TestBuilders:
    @pytest.mark.parametrize("P", [1, 2, 3, 4, 8])
    def test_token_ring_bidir_counts(self, P):
        from repro.core.token_ring import token_ring_bidir_schedule

        s = token_ring_bidir_schedule(P)
        s.validate({"qa", "qb", "kv", "aa", "ab"})
        computes = sum(len(st.computes) for st in s.all_steps())
        assert computes == 2 * P  # two halves, P blocks each
        if P == 1:
            assert _send_hops(s, "qa") == {}
            return
        # q: P-1 hops; accumulator: P-1 pipelined + 1 going home = P.
        assert _send_hops(s, "qa") == {1: P - 1}
        assert _send_hops(s, "aa") == {1: P}
        assert _send_hops(s, "qb") == {-1: P - 1}
        assert _send_hops(s, "ab") == {-1: P}
        # resident KV never enters the scan carry
        assert "kv" in s.static

    @pytest.mark.parametrize("P", [1, 2, 3, 4, 8])
    def test_token_ring_faithful_counts(self, P):
        from repro.core.token_ring import token_ring_faithful_schedule

        s = token_ring_faithful_schedule(P)
        s.validate({"q", "kv", "acc"})
        assert sum(len(st.computes) for st in s.all_steps()) == P
        if P == 1:
            return
        assert _send_hops(s, "q") == {1: P - 1}
        # homeward partial sends: exactly one per distance 1..P-1
        assert _send_hops(s, "p") == {-i: 1 for i in range(1, P)}

    @pytest.mark.parametrize("P", [1, 2, 3, 4, 8])
    def test_ring_counts(self, P):
        from repro.core.ring_attention import ring_bidir_schedule, ring_schedule

        s = ring_schedule(P)
        s.validate({"q", "kv", "acc"})
        assert sum(len(st.computes) for st in s.all_steps()) == P
        assert _send_hops(s, "kv") == ({1: P - 1} if P > 1 else {})

        sb = ring_bidir_schedule(P)
        sb.validate({"q", "kva", "kvb", "acc"})
        assert _send_hops(sb, "kva") == ({1: P - 1} if P > 1 else {})
        assert _send_hops(sb, "kvb") == ({-1: P - 1} if P > 1 else {})

    @pytest.mark.parametrize("halo", [0, 1, 3])
    def test_window_halo(self, halo):
        from repro.core.window import window_halo_schedule

        s = window_halo_schedule(halo)
        s.validate({"q", "kv0"})
        (compute,) = s.all_steps()[-1].computes
        # oldest predecessor first, local shard last — contiguous order
        assert compute.kv == tuple(f"kv{j}" for j in range(halo, -1, -1))
        assert sum(len(st.sends) for st in s.all_steps()) == halo

    def test_pipelined_body_sends_are_entry_generation(self):
        """The IR-level overlap property: no body Send reads a buffer that a
        Compute (or Merge) of the same step writes — every payload exists at
        step entry."""
        from repro.core.ring_attention import ring_bidir_schedule, ring_schedule
        from repro.core.token_ring import token_ring_bidir_schedule

        for sched in (
            token_ring_bidir_schedule(4),
            ring_schedule(4),
            ring_bidir_schedule(4),
        ):
            body = sched.body
            step_writes = {c.out for c in body.computes}
            for op in body.sends:
                assert not (set(op.buffers) & step_writes), (
                    f"send of {op.buffers} would wait on this step's compute"
                )


# ---------------------------------------------------------------------------
# device-free executor run against the attention oracle


def test_executor_merges_match_oracle():
    """Two KV halves computed as separate blocks and folded with Merge()
    equal one full-attention pass — the executor's Compute+Merge pipeline is
    the paper's Update() decomposition."""
    from repro.core.merge import empty_partial, finalize
    from repro.kernels.ref import attention_reference

    rng = np.random.default_rng(3)
    B, S, H, D = 2, 16, 2, 8
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def compute(qq, qp, kk, vv, kp):
        return attention_reference(
            qq, kk, vv, causal=True, q_pos=qp, k_pos=kp, return_lse=True
        )

    half = S // 2
    bufs = {
        "q": (q, pos),
        "kva": (k[:, :half], v[:, :half], pos[:, :half]),
        "kvb": (k[:, half:], v[:, half:], pos[:, half:]),
        "acc": empty_partial(q.shape),
    }
    sched = Schedule(prologue=(
        Step(Compute("q", ("kva",), "p"), Merge("acc", "p")),
        Step(Compute("q", ("kvb",), "p"), Merge("acc", "p")),
    ))
    res = execute_schedule(sched, bufs, axis_name=None, compute_fn=compute)
    out, _ = finalize(*res["acc"])
    ref, _ = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)

    # the concat path: both halves in one Compute equals the same oracle
    bufs2 = dict(bufs, acc=empty_partial(q.shape))
    sched2 = Schedule(prologue=(
        Step(Compute("q", ("kva", "kvb"), "p"), Merge("acc", "p")),
    ))
    res2 = execute_schedule(sched2, bufs2, axis_name=None, compute_fn=compute)
    out2, _ = finalize(*res2["acc"])
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref), atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# ring name scopes (four fake devices, in a subprocess)


def test_ring_step_scopes_named_and_metadata_only():
    """A four-device TokenRing step's compiled op paths carry ``ring_send``,
    ``ring_compute`` and ``ring_merge`` in its forward and backward, and its
    outputs are bitwise those of the same step without the scopes."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(repo, "src"), REPRO_CHECK_DEVICES="4")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.testing.strategy_check", "scopes"],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "PASS ring scopes" in proc.stdout
