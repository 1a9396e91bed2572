"""The compiler's accounting of the train step, per device: arguments plus
temporaries (outputs alias the donated arguments)."""


def read(sources):
    m = sources.get("step_memory")
    if not m:
        return None
    return (m["arguments"] + m["temp"] + max(m["outputs"] - m["aliased"], 0)) / 2 ** 30
