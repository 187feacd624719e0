"""Paper Figure 6 analog: per-step time of the attention schedule.

The paper profiles a 4-GPU A10 node at seq 24 000 (LLaMA2-7B attention) and
finds: Ring-Attention steps are communication-bound (~7.6 ms) while TokenRing
overlaps Q/out transfers with compute (~3.5-4.6 ms per step).

On the TPU target we model per-step time as max(compute, max-direction comm)
— the overlap assumption both the paper and XLA's async collectives make —
using v5e constants, for each strategy.  We also *measure* wall-clock on 4
simulated host devices (schedule correctness, not bandwidth, is what CPU
timing validates; the modeled numbers are the roofline-grade result).

Run directly (sets device count before jax import):
  PYTHONPATH=src python -m benchmarks.bench_attention_steps
"""

import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=4 "
        + os.environ.get("XLA_FLAGS", "")
    )

PEAK_FLOPS = 197e12
LINK_BW = 50e9

# table label -> (registered strategy, cost-model extras); per-step bytes are
# the registered whole-pass comm_cost divided by the (P-1) ring steps, which
# amortizes TokenRing's going-home hop across the pass.
STEP_ROWS = {
    "ring-attention": ("ring", {}),
    "ring-bidir": ("ring_bidir", {}),
    "tokenring": ("tokenring", {"travel_dtype": "bfloat16"}),
}


def modeled_step_times(S=24000, Hq=32, Hkv=32, Dh=128, P=4, b=2):
    """Per-ring-step (compute, comm, step) seconds for each strategy."""
    from repro.core.strategies import get_strategy, strategy_cost

    S_loc = S // P
    # per-step block attention flops: q_loc x kv_loc (causal-balanced ~ x0.5)
    flops = 4 * S_loc * S_loc * Hq * Dh * 0.5
    t_comp = flops / PEAK_FLOPS
    res = {}
    for name, (strategy, extra) in STEP_ROWS.items():
        cost = strategy_cost(
            get_strategy(strategy), 1, S, Hq, Hkv, Dh, P,
            bytes_per_elem=b, **extra,
        )
        t_comm = cost.max_direction / (P - 1) / LINK_BW
        res[name] = (t_comp, t_comm, max(t_comp, t_comm))
    return res


def run():
    rows = []
    print("\n### Figure-6 analog (modeled, v5e): per-step times, llama2-7b attn")
    print("seq 24000, 4 devices, batch 1 | compute ms | comm ms | step ms |")
    for name, (tc, tm, ts) in modeled_step_times().items():
        print(f"| {name} | {tc*1e3:.2f} | {tm*1e3:.2f} | {ts*1e3:.2f} |")
        rows.append((f"fig6_model/{name}", ts * 1e6, f"comm={tm*1e3:.2f}ms"))
    # the paper's observed ratio: ring comm-bound vs tokenring compute-bound
    m = modeled_step_times()
    ratio = m["ring-attention"][2] / m["tokenring"][2]
    print(f"ring/tokenring step-time ratio: {ratio:.2f}x "
          "(paper: 7.6ms vs 3.5-4.6ms ~= 1.7-2.2x)")
    rows.append(("fig6_model/ring_over_tokenring", ratio, "paper ~1.7-2.2x"))
    return rows


def measure_wallclock():
    """CPU wall-clock of the actual schedules on 4 simulated devices."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ParallelContext, sp_attention
    from repro.core.compat import make_mesh
    from repro.core.strategies import get_strategy, ineligible_reason, registered_strategies
    from repro.core.zigzag import to_zigzag

    mesh = make_mesh((1, 4), ("data", "model"))
    S, Hq, Dh = 24000 // 5, 32, 64  # scaled for CPU (shape-preserving)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((1, S, Hq, Dh)), jnp.float32)
    pos = to_zigzag(jnp.arange(S, dtype=jnp.int32)[None, :, None], 4, axis=1)[0, :, 0]
    qz = to_zigzag(q, 4, axis=1)
    rows = []
    runnable = [
        d.name for d in registered_strategies()
        if ineligible_reason(d, Hq=Hq, Hkv=Hq, P=4, layout="zigzag") is None
        and d.ring_axes == 1  # two-axis rings need a (pod, inner) mesh
    ]
    for strategy in runnable:
        pctx = ParallelContext(
            mesh=mesh, data_axis=None, sp_axes=("model",), strategy=strategy,
            impl="xla", block_q=512, block_k=512,
        )
        fn = jax.jit(
            lambda q, p: sp_attention(q, q, q, p, p, pctx=pctx, causal=True)
        )
        fn(qz, pos).block_until_ready()  # compile
        t0 = time.perf_counter()
        n = 3
        for _ in range(n):
            fn(qz, pos).block_until_ready()
        dt = (time.perf_counter() - t0) / n
        print(f"| measured(cpu,4dev) {strategy} | {dt*1e3:.1f} ms/pass |")
        rows.append((f"fig6_cpu/{strategy}", dt * 1e6, "wall"))
    return rows


if __name__ == "__main__":
    run()
    measure_wallclock()
