def read(run):
    """For an earlier line: the loss at the window's ends and the steps."""
    w = run.window
    if w["kind"] != "train":
        return None
    return {"steps": w["steps"], "first": w["losses"][0],
            "last": w["losses"][-1],
            "live_arrays_peak_bytes": w["memory"],
            "program_scratch_bytes": w["program_scratch"]}
