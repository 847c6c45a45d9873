"""Pages the group's trainer process touched for the first time a step:
median growth of `commit_gate.minflt` between consecutive gates. Times
the page size (4 KiB unless the kernel gave huge pages, which count one
fault each) it stands beside `ar_pack_fresh_bytes_step` and
`wire_fresh_bytes_step`. None where the gates carry no such field, and
None where it reads 0 at every gate: that kernel does not count faults
(`runsc`, on the machines the chip tool hands out), and
`host_sys_ms_step` is what it does fill. No cell of BENCHMARK.json lists
this metric while the benchmark's machines run that kernel."""

from benchmark import wait_readers


def read(run):
    return wait_readers.per_gate(run, "minflt")
