"""Seconds the build of the boosting program took that the window's
first job ran: trace, lowering and XLA's compile, or the load from the
persistent cache, whenever it was built (in set-up, by the warm job).
Source: `model.training_profile["device_loop.program_build_s"]`, the
seconds of the host span `ydf.device_loop.compile` that the device loop
keeps with the compiled function. A program without that key (an older
one) gives nothing. Layer `ops.device_loop`; moves setup_s."""

META = {
    "layer": "ops.device_loop",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "moves": "setup_s",
    "workloads": None
}

def read(run):
    if not run["jobs"]:
        return None
    return run["jobs"][0]["profile"].get("device_loop.program_build_s")
