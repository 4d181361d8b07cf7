"""The chip benchmark's harness: layout, device checks, traffic, the
serving loop, the trace reduction and the check against the reference.

It imports nothing of the program at module level; the program is
loaded by ``chipbench.cell`` once the device has been checked."""
