"""Run EVERY reference YAML REST suite against the in-process Node and
report which pass completely (candidates for tests/test_yaml_rest.py's
CURATED list). One fresh Node per test case, like the test runner."""
import json
import os
import sys
import traceback

# a correctness sweep: pinned to the CPU backend, before jax loads
os.environ["JAX_PLATFORMS"] = "cpu"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tests"))

import yaml_rest_runner as yr  # noqa: E402
from opensearch_tpu.node import Node  # noqa: E402


def main():
    results = {}
    suites = []
    for root, _dirs, files in os.walk(yr.TEST_DIR):
        for f in files:
            if f.endswith(".yml"):
                suites.append(os.path.relpath(os.path.join(root, f),
                                              yr.TEST_DIR))
    suites.sort()
    for suite in suites:
        path = os.path.join(yr.TEST_DIR, suite)
        try:
            setup, teardown, tests = yr.load_suite(path)
        except Exception as e:
            results[suite] = {"load_error": str(e)[:120]}
            continue
        n_pass = n_skip = 0
        fails = []
        for name, steps in tests:
            node = Node()
            try:
                yr.run_case(node, setup, steps)
                n_pass += 1
            except yr.SkipTest:
                n_skip += 1
            except Exception as e:
                fails.append(f"{name}: {type(e).__name__}: {str(e)[:100]}")
        results[suite] = {"pass": n_pass, "skip": n_skip,
                          "fail": len(fails), "fails": fails[:2]}
        status = "FULL" if not fails and n_pass > 0 else \
            ("EMPTY" if n_pass == 0 and not fails else "PART")
        print(f"{status} {suite} pass={n_pass} skip={n_skip} "
              f"fail={len(fails)}", flush=True)
    full = [s for s, r in results.items()
            if r.get("fail") == 0 and r.get("pass", 0) > 0]
    print(f"\nFULL PASS: {len(full)}/{len(suites)}")
    with open(os.path.join(REPO, "YAML_SWEEP.json"), "w") as f:
        json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
