"""Benchmark harness: one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run

Prints ``name,value,paper_or_derived[,rel_err]`` CSV lines.
"""

from __future__ import annotations


def _emit(rows):
    for r in rows:
        err = r.get("rel_err")
        tail = f",{err}" if err is not None else ""
        print(f"{r['name']},{r['value']},{r['paper']}{tail}")


def main() -> None:
    from benchmarks import api_bench, freq, sweep_bench, tables

    print("# freq (paper §5.2)")
    _emit(freq.run())
    print("# api (Simulator session: cache + run_many + engine agreement)")
    _emit(api_bench.run())
    print("# table3 (paper Table 3 / Fig 8)")
    _emit(tables.run_table3())
    print("# table4 (paper Table 4 / Fig 9)")
    _emit(tables.run_table4())
    print("# table5 (paper Table 5 / Fig 10)")
    _emit(tables.run_table5())
    print("# design-space sweep engines")
    _emit(sweep_bench.run())


if __name__ == "__main__":
    main()
