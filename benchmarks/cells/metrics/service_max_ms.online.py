"""The longest service time of one launch in the window, in
milliseconds, as the executor measured it (``Response.complete_s -
Response.launch_s``): where a stall of the launch path shows."""


def read(view):
    s = view.ans.get("service_s")
    if not s:
        return None
    return 1e3 * max(s)
