"""FLOPs the steps of the traced slice need (the family's `step_work`:
GEMMs, causal attention over the real context, the LM head only where its
logits are used) over the slice's seconds times the chip's bf16 peak, in
percent."""


def read(run):
    t = run["trace"]
    if not t or not t["window_s"] or not run["steps"]:
        return None
    work = run["family"].step_work
    need = sum(work(run["dims"], s)[0] for s in run["steps"])
    return 100.0 * need / (t["window_s"] * run["peak"]["bf16_flops"])
