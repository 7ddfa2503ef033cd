"""Batched serving with continuous slot refill (see serve/engine.py).

  PYTHONPATH=src python examples/serve_demo.py
"""

from repro.launch import serve


def main():
    engine = serve.main(["--arch", "mixtral-8x7b", "--requests", "6",
                         "--slots", "3", "--max-new", "12",
                         "--max-len", "64"])
    results = {r.uid: r.out for r in engine.finished}
    assert len(results) == 6
    assert all(len(v) == 12 for v in results.values())
    print("serve_demo OK")


if __name__ == "__main__":
    main()
