"""``train_rows_per_s``: rows times epochs trained by the train queries
completed in the window, over the window (its start to the last
completion); the grid's models share each pass, so the count is not
multiplied by their number."""


def read(run):
    rows = sum(q.template.kind.work_rows(q.template.spec, run.sizes)
               for q in run.completed
               if q.template.spec["kind"] == "train_glm")
    if not rows or run.window_s <= 0:
        return None
    return rows / run.window_s
