"""Megabytes (10^6 bytes) of the packed announcer matrix put whole on the
device in the window, over the window's solve epochs, timed or not: what
`decision.tpu.mbuf_put_bytes` gained (every whole put of `d_mbuf` adds its
bytes: 6 planes x rows x advertisers x 4). 0 under link or prefix events,
which scatter single cells; one matrix an epoch where every epoch drains a
switch or gives one back. Read as prefix_rows_changed_per_epoch reads its
counter: the program stamps every addition as a sample of the stat of the
same name, and the samples since the window's start are summed. A program
without the counter, or a call with no window observed, gives None."""

import window_counter


def read(series: dict):
    put = window_counter.per_epoch(series, "decision.tpu.mbuf_put_bytes")
    return None if put is None else put / 1e6
