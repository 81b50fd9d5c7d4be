"""Elements a device call of the resolution engine carried, on average:
the program's ``elements.<kernel>@<platform>`` counters (unpadded element
counts) over its ``dispatch.<kernel>@<platform>`` counters.

The run record holds the dispatch counts but not the element counters,
so this reads the program's registry as the window left it:
``drivers/simulate.py`` clears it where the window starts, and nothing of
the program runs after the window (the reference imports none of it).  A
program without the registry gives nothing."""


def read(run):
    try:
        from repro import trace
    except ImportError:
        return None
    c = trace.counts()
    elems = sum(v for k, v in c.items() if k.startswith("elements."))
    calls = sum(v for k, v in c.items() if k.startswith("dispatch."))
    return elems / calls if elems and calls else None
