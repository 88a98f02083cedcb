"""The compiler's ``memory_analysis()`` of the step program: arguments +
temporaries, per chip (outputs alias the donated arguments)."""


def read(run):
    c = run["worker"]["compile"]
    return (c["argument_bytes"] + c["temp_bytes"]) / 1e9
