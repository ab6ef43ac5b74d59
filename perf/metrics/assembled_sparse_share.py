"""Share of the columns the window's assemblers made that came out as padded
CSR, in per cent: the program's counters `assembler.sparse_out` over it and
`assembler.dense_out`, one tick an assembled column. 100 where every one-hot
input stayed sparse; 0 would say the assembler densified them. Nothing where
neither was counted: no assembler ran, or an older program, which has no such
counters."""


def read(run):
    counters = run["counters"]
    sparse, dense = counters.get("assembler.sparse_out", 0), counters.get("assembler.dense_out", 0)
    if not sparse + dense:
        return None
    return 100.0 * sparse / (sparse + dense)
