def stage(pipe, clock, steps, watermarks):
    # the one staging loop: the only stage.fill / stage.put sections
    with dispatch_stage(clock, "stage.fill"):  # noqa: F821
        host = pipe.fill(steps, watermarks)
    with dispatch_stage(clock, "stage.put"):  # noqa: F821
        return pipe.put(host)
