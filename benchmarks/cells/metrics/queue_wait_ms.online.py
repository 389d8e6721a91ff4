"""Mean wait of a served request in the scheduler's queue, in
milliseconds: its launch time minus its admission time, both on the
scheduler's wall clock (``Response.launch_s - Response.arrival_s``)."""


def read(view):
    w = view.ans.get("queue_wait_s")
    if not w:
        return None
    return 1e3 * sum(w) / len(w)
