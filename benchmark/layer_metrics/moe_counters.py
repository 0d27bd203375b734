"""The routing counters of the window: the args of the program's
`serving.step_counters` spans (one a batch, read from the device once)
that began inside it, summed. Not a metric: three readers share it."""


def window_counters(run):
    """({counter name, as the program's registry has it (`moe.*`): sum
    over the window's batches}, the model table the program published),
    or None where the program has neither."""
    from paddle_tpu import observability as obs

    model = obs.get_tables().get("serving.generate.model")
    spans = [s for s in run.spans if s["name"] == "serving.step_counters"]
    if not model or not spans:
        return None
    totals = {}
    for s in spans:
        for key, value in s["args"].items():
            if isinstance(value, (int, float)):
                totals[key] = totals.get(key, 0) + value
    return totals, model
