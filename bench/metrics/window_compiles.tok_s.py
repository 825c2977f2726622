"""Programs compiled or loaded from the persistent cache inside the window
(JAX monitoring events): each one stalls the loop that needed it."""


def read(win):
    return win.compiles
