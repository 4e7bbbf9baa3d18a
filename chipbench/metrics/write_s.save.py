"""Host seconds per save outside the device waves: serialization, the
cluster write, the writes by reference, and the retention that follows
(delete of the oldest, GC drained), from the benchmark's spans."""


def read(ctx):
    s = ctx["spans"]
    saves = s.get("save.write", [])
    if not saves:
        return None
    return (sum(saves) - sum(s.get("save.waves", [])) + sum(s.get("save.retire", []))) / len(saves)
