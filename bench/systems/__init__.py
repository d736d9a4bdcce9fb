"""The systems a cell can drive, one module each, chosen by the
configuration's ``system``; and what their measured windows share."""
import contextlib
import gc


@contextlib.contextmanager
def quiet_host():
    """The measured window's host side: the set-up's garbage collected
    before it, and no collection inside it, so that a pause of the
    collector does not stall the dispatch of the next step."""
    gc.collect()
    gc.disable()
    try:
        yield
    finally:
        gc.enable()
