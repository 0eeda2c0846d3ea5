"""Engine: token rows the decode steps' full-kind gathers touch (the step's rung K x its live slots, serving/decode_gathered_tokens) over the tokens resident in those slots (serving/kv_sampled_tokens), both added up at every decode step: 1 would be a step that reads what it needs; one long slot sets the rung that every slot pays."""


def read(run):
    gathered = run.counters.get("serving/decode_gathered_tokens")
    resident = run.counters.get("serving/kv_sampled_tokens")
    if not gathered or not resident:
        return None
    return gathered / resident
