"""Engine: the extend programs' share of the window's device time, %: how much of the cell the tail-over-latent-context form is (jit_extend_impl_T*: here the question tails over 8k-30k cached latent rows). The device times are the traced slice's, which holds one whole turn-over of the slots (32 tails) and decode steps on both sides: the mean device time of an extend launch and of a decode step, each weighted by the window's own count (the serving/prefill histogram's samples; serving/decode_steps). The slice's literal share, extends over its busy time, swings with how far the profiler's Python tracer stretches the turn-over's host work and so with the decode steps the slice holds: 32.2 and 42.4 in two runs that read 22.4 and 21.7 here (my chip runs, PR 32)."""

import statistics


def read(run):
    if run.trace is None:
        return None
    extend = run.trace.module_durations(r"extend_impl")
    decode = run.trace.module_durations(r"decode_impl")
    launches = len(run.hists.get("serving/prefill") or ())
    steps = run.counters.get("serving/decode_steps")
    if not extend or not decode or not launches or not steps:
        return None
    tails = statistics.fmean(extend) * launches
    return 100.0 * tails / (tails + statistics.fmean(decode) * steps)
