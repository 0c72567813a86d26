def stage_on_mesh(pipe, clock, steps, watermarks):
    # SEEDED: a sibling staging method with its own stage.fill section
    with dispatch_stage(clock, "stage.fill"):  # noqa: F821
        return pipe.deal(pipe.fill(steps, watermarks))
