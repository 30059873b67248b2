"""Process: the CPU the whole process used while it stood still, in
cores - window total of ``runtime.stall_cpu_s`` (each witnessed stall's
lateness times the cores the process, all threads, kept busy over it)
over that of ``runtime.stall_s``.  Near 0: every thread was off the CPU
(the kernel, I/O, page faults, the host); about 1: one thread computed,
holding the interpreter.  Not a share: it can pass 1.  0 where nothing
stalled in the window (read it beside ``stall_ms_in_window``, which is
then 0 too), so a traced line of every listed cell carries it.  None
where the program keeps no such timer."""


def read(run):
    if "runtime.stall_cpu_s" not in run.marks1:
        return None
    stalled, _ = run.timer("runtime.stall_s")
    cpu, _ = run.timer("runtime.stall_cpu_s")
    return cpu / stalled if stalled > 0 else 0.0
