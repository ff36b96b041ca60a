"""1 - union of device-operation intervals over the traced stretch; on
several chips the chip that idles most."""


def read(run, params):
    if run.reduced is None:
        return None
    share = run.reduced.idle_share()
    return None if share is None else 100.0 * share
