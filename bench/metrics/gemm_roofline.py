"""Share of the roofline reached by the routed GEMMs in the traced slice:
the roofline time of the GEMMs the steps need (`bench.flops.step_gemms`,
each GEMM its own kernel) over the device time of the ops under
`dit_gemm.*` scopes, in percent.

The two sides count the same GEMMs only while each step's program routes
every one of them: one kernel per projection of the layer inside the layer
loop, and the LM head outside it (`run["routed_gemms"]`, from the compiled
programs). Where a GEMM has left the routed path, its needed time would
stay above the line while its device time left the line below, so the
metric reads nothing."""
from bench import flops


def read(run):
    t = run["trace"]
    if not t or not t["gemm_s"] or not run["steps"]:
        return None
    per_layer = len(flops.layer_gemms(run["dims"], 1))
    for kind in {s["kind"] for s in run["steps"]}:
        inside, outside = run["routed_gemms"].get(kind, (0, 0))
        head = any(s["logits_rows"] for s in run["steps"]
                   if s["kind"] == kind)
        if inside != per_layer or outside > 1 or (head and not outside):
            return None
    need = sum(flops.gemm_roofline_s(
        flops.step_gemms(run["dims"], s["batch"], s["q_len"],
                         s["logits_rows"]), run["peak"])
        for s in run["steps"])
    return 100.0 * need / t["gemm_s"]
