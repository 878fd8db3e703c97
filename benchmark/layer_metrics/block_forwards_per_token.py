def read(run, spec):
    """Slot forwards over tokens yielded, both summed over the traced
    decode steps' spans that carry ``yielded``; None where none does (a
    program that decodes one token a slot a step)."""
    spans = [s["attrs"] for s in run.get("spans") or ()
             if s["name"] == spec["span"]
             and s.get("attrs", {}).get(spec["tokens"]) is not None
             and s["attrs"].get(spec["forwards"]) is not None]
    tokens = sum(a[spec["tokens"]] for a in spans)
    return sum(a[spec["forwards"]] for a in spans) / tokens if tokens \
        else None
