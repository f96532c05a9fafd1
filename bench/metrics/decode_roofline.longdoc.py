"""Programs: the decode program's roofline share in the traced window
(%): the least time for the traced decode calls (every weight read once,
each active slot's recurrent state read and written, its live KV read;
the configuration's ``decode_work``) over the decode program's device
time, the union of its operations' intervals.  The program has no kernel
of its own (its attention runs on the XLA gather, its Mamba-2 recurrence
in XLA), so the whole program stands in for one."""
from bench import trace_reduce

MODULE = "_decode_and_sample"


def program_ns(tr, module: str, lo: float, hi: float) -> float:
    """Device time in [lo, hi) in which an operation of ``module`` ran."""
    keep = [i for i, m in enumerate(tr.module) if m == module]
    ops = trace_reduce.Trace(tr.start[keep], tr.end[keep],
                             [tr.op[i] for i in keep], [module] * len(keep))
    return trace_reduce.busy_ns(ops, lo, hi)


def read(run):
    if run.trace is None or not run.peaks:
        return None
    fam, pub = run.cell.family, run.cell.config["published"]
    if not hasattr(fam, "decode_work"):
        return None
    block = run.engine["kv_block_size"]
    flops = byts = 0.0
    for t in run.traced_ticks():
        if t.decode_ctx:
            f, b = fam.decode_work(pub, t.decode_ctx, block)
            flops += f
            byts += b
    ns = program_ns(run.trace, MODULE, run.trace_lo, run.trace_hi)
    if not ns or not (flops or byts):
        return None
    least = max(flops / run.peaks["bf16_flops"],
                byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (ns * 1e-9)
