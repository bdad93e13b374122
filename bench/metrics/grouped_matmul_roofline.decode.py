"""The grouped-matmul kernel's share of its roofline over traced decode
steps (profiler trace): the need of the steps' ``grouped_matmul`` ops at
the chip's peaks over the device time of the ``grouped_matmul`` Pallas
calls among the plan trace's top ops.

The top ops are a capped list, so a kernel call can fall out of it; each
grouped-matmul site of a step is one kernel instruction, so the reading is
None unless every site's instruction shows (as it is where no call shows):
a missing call would leave its need over the others' time."""
from harness import work

KERNEL = "grouped_matmul"
PALLAS = "tpu_custom_call"


def _is_kernel(name: str) -> bool:
    inst, _, rest = name.partition(" ")
    return inst.split(".")[0] == KERNEL and rest.endswith(PALLAS)


def read(ctx):
    s = ctx.get("plan")
    if ctx.get("phase") != "decode" or s is None or s.n_steps == 0:
        return None
    kernels = {name: t for name, t in s.top_ops if _is_kernel(name)}
    sites = {op["name"] for ops in ctx["step_ops"] for op in ops
             if op["kind"] == KERNEL}
    seconds = sum(kernels.values())
    if seconds <= 0 or len(kernels) < len(sites):
        return None
    peaks, need = ctx["peaks"], 0.0
    for ops in ctx["step_ops"]:
        gmm = [op for op in ops if op["kind"] == KERNEL]
        fl, by = work.totals(gmm, ctx["itemsize"])
        need += max(fl / peaks["bf16_flops_per_s"],
                    by / peaks["hbm_bytes_per_s"])
    if need <= 0:
        return None
    return 100.0 * need / seconds
