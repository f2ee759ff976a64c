"""Drive one rehearsal run of a cell with the timed path broken underneath; print ``correct``.

    python3 benchmark/tests/_drive_fault.py <workload> <unchanged|stats_unchanged|half_batch|no_exchange|unpriced_kernel|none>

Skips the harness's look for a chip (it is the CPU rehearsal) and drives the
rest of a run: the program built as ever, its first three steps, a short
window, the reference, the verdict.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


UNPRICED_KERNEL = """
%zz_computation (p: bf16[8,128]) -> bf16[8,128] {
  %p = bf16[8,128]{1,0} parameter(0)
  ROOT %zz_kernel.1 = bf16[8,128]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(M)/zz_unpriced_kernel/pallas_call"}, backend_config={"custom_call_config":{"body":"..."}}
}
"""


def main(workload: str, fault: str) -> int:
    from benchmark import files

    cell = files.load_json("workloads", workload)
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={cell['chips']}"
    import jax
    import jax.numpy as jnp

    def unchanged(step):  # a step that returns its state as it got it
        def wrapped(state, batch, lr, rng):
            keep = jax.tree.map(jnp.copy, state)
            _, metrics = step(state, batch, lr, rng)
            return keep, metrics
        return wrapped

    def stats_unchanged(step):  # the running statistics never updated
        def wrapped(state, batch, lr, rng):
            keep = jax.tree.map(jnp.copy, state.batch_stats)
            new_state, metrics = step(state, batch, lr, rng)
            return new_state.replace(batch_stats=keep), metrics
        return wrapped

    def half_batch(step):  # the second half left out, the mean taken over the rest
        def wrapped(state, batch, lr, rng):
            def first_half_twice(x):
                n = x.shape[0] // 2
                return jnp.concatenate([x[:n], x[:n]])
            return step(state, jax.tree.map(first_half_twice, batch), lr, rng)
        return wrapped

    wrapper = {"unchanged": unchanged, "stats_unchanged": stats_unchanged, "half_batch": half_batch}.get(fault)
    if fault == "no_exchange":  # the mean over the data axis left out of the step as it is traced
        jax.lax.pmean = lambda x, axis_name, **kw: x

    from benchmark import harness

    if fault == "unpriced_kernel":  # the CPU's step holds no kernel: put one into its text
        lower = harness.Program.lower_step_text
        harness.Program.lower_step_text = lambda self, batch_abs: lower(self, batch_abs) + UNPRICED_KERNEL

    result = harness.run_cell(workload, seed=41, seconds=1.0, trace=fault == "unpriced_kernel", rehearse=True,
                              out_root=os.path.join(ROOT, "benchmark_out", "faults", fault), step_wrapper=wrapper)
    print(json.dumps({"correct": result["correct"], "compared": result["compared"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1], sys.argv[2]))
