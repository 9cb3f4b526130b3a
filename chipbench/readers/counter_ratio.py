"""A counter's growth over the window as a share of another's, or of
the lanes the window sent (``"lanes_sent"``)."""


def read(ev, num, den, scale=100.0):
    top = ev.counter_delta(num)
    bottom = ev.lanes_sent if den == "lanes_sent" else ev.counter_delta(den)
    if not bottom:
        return None
    return scale * top / bottom
