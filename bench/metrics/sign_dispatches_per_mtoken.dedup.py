"""Device dispatches of the streaming signer per million tokens signed.

Read from the program's counter ``repro.kernels.stream.dispatch_count()``
in the thread that calls ``add_batch``, over the window.
"""


def read(facts, trace, peaks):
    if "sign_dispatches" not in facts or not facts["tokens"]:
        return None
    return facts["sign_dispatches"] / (facts["tokens"] / 1e6)
