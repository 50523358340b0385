"""Mean time a traced step waited for its batch: the program's
``train.batch`` spans inside the traced window, per traced step, in
milliseconds."""


def read(r):
    s = r["spans"].get("train.batch")
    if not s or not r["steps"]:
        return None
    return 1e3 * s[0] / r["steps"]
