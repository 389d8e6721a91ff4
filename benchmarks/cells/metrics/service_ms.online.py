"""Mean service time of a launch as the executor measured it, in
milliseconds, over served requests (``Response.complete_s -
Response.launch_s``): the packed program's call from the host, without
the scheduler's packing."""


def read(view):
    s = view.ans.get("service_s")
    if not s:
        return None
    return 1e3 * sum(s) / len(s)
