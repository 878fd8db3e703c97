from lib import readers


def read(run, spec):
    steps = run["facts"].get("traced_steps")
    secs = readers.device_seconds(run, spec["events"])
    if not secs or not steps or not run.get("peaks"):
        return None
    least = run["facts"]["attention_flops_per_step_per_chip"] * steps \
        / run["peaks"]["bf16_flops_per_s"]
    return 100.0 * least / secs
