"""Share of the roofline reached by the routed GEMMs in the traced slice:
the roofline time of the GEMMs the steps need (the family's `step_gemms`,
each GEMM its own kernel) over the device time of the ops under
`dit_gemm.*` scopes, in percent.

The two sides count the same GEMMs only while each step's program routes
every one of them: the routed kernels the family expects inside the layer
loop, and outside it no more than it expects, with the LM head among them
where the step uses logits (`run["routed_gemms"]`, from the compiled
programs). Where a GEMM has left the routed path, its needed time would
stay above the line while its device time left the line below, so the
metric reads nothing."""
from bench.flops import gemm_roofline_s


def read(run):
    t = run["trace"]
    if not t or not t["gemm_s"] or not run["steps"]:
        return None
    fam, dims = run["family"], run["dims"]
    for kind in {s["kind"] for s in run["steps"]}:
        inside, outside = run["routed_gemms"].get(kind, (0, 0))
        want_in, want_out = fam.routed_expected(dims, kind)
        head = any(s["logits_rows"] for s in run["steps"]
                   if s["kind"] == kind)
        if inside != want_in or outside > want_out or (
                head and outside < want_out):
            return None
    need = sum(gemm_roofline_s(fam.step_gemms(dims, s), run["peak"])
               for s in run["steps"])
    return 100.0 * need / t["gemm_s"]
