"""Messenger: frames delivered per delivery burst over the window
(msg/messenger.py LocalBus ``frames_delivered`` / ``delivery_bursts``)."""


def read(w):
    bursts = w.delta("bus.delivery_bursts")
    if bursts <= 0:
        return None
    return w.delta("bus.frames_delivered") / bursts
