"""All output tokens of the window (whoever they were for) over its
length."""


def read(run, params):
    w = run.result["window"]
    if "tokens_in_window" not in w:
        return None
    return w["tokens_in_window"] / w["seconds"]
