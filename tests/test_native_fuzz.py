"""Byte-fuzz the C++ data-plane parser (crash safety, not parity).

The native extension parses UNTRUSTED bytes — metric-store HTTP response
bodies — inside the engine process. tests/test_native.py pins parity and a
handful of known-hostile shapes; this file hammers the same entry points
with thousands of seeded random mutations of valid bodies plus structured
adversarial cases (NaN timestamps — a strict-weak-ordering UB crash vector
in std::stable_sort before the round-5 fix; 1e300 timestamps — double->long
cast UB; deep nesting; truncations; invalid UTF-8). The reference has no
equivalent component (its Go services unmarshal into typed structs and get
memory safety from the runtime, foremast-service/pkg/prometheus/*.go); a
C++ parser must earn that safety by test.

Two legs:
  * subprocess no-crash leg — the corpus runs in a child so a segfault
    fails THIS test instead of killing the pytest process;
  * ASAN leg — same corpus against a -fsanitize=address build (via the
    loader's FOREMAST_NATIVE_SO/FOREMAST_NATIVE_CXXFLAGS seams), catching
    silent out-of-bounds reads that do not crash. Skipped when libasan is
    not present in the toolchain image.

Invariants checked per case (when the parser accepts the body):
  parse_series: len(ts) == len(vals); non-NaN timestamps nondecreasing
  (NaNs, if any, partitioned to the tail by design).
  parse_grid:   len(vals) == len(mask) <= max_steps, float32/bool dtypes.
  resample:     output length exactly max(1, (end-start)//step).
"""
from __future__ import annotations

import os
import random
import subprocess
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from foremast_tpu import native  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_CASES = int(os.environ.get("FUZZ_CASES", "4000"))
N_CASES_ASAN = int(os.environ.get("FUZZ_CASES_ASAN", "1500"))
SEED = 20260731

_PROM = (b'{"status":"success","data":{"resultType":"matrix","result":'
         b'[{"metric":{"__name__":"up","job":"api"},"values":'
         b'[[1700000000,"1.5"],[1700000060,"2"],[1700000120,"NaN"],'
         b'[1700000180,"+Inf"]]},'
         b'{"metric":{"job":"api2"},"values":[[1700000000,"3"]]}]}}')
_WF = (b'{"query":"ts(x)","timeseries":[{"label":"x","data":'
       b'[[1700000000,1.5],[1700000060,2.25],[1700000120,null]]}],'
       b'"stats":{"keys":3}}')
_BIG = (b'{"data":{"result":[{"values":[' +
        b",".join(b"[%d,\"%d.5\"]" % (1700000000 + 60 * i, i)
                  for i in range(300)) + b']}]}}')
_BASES = [
    _PROM,
    _WF,
    _BIG,
    b'{"status":"success","data":{"result":[]}}',
    b'{"timeseries":[]}',
    b'[]',
    b'{}',
    b'{"data":{"result":[{"values":[[1700000000,"\\u00e9\\n\\t"]]}]}}',
]

# structured adversarial cases, always included ahead of the random corpus
_DIRECTED = [
    # NaN / inf timestamps (strtod accepts them even though JSON forbids):
    # pre-fix these hit stable_sort comparator UB
    b'{"data":{"result":[{"values":[[nan,2],[1700000000,"1"],[nan,3]]}]}}',
    b'{"data":{"result":[{"values":[[NaN,2],[inf,"1"],[-inf,4]]}]}}',
    # huge finite timestamps: pre-fix double->long cast UB in fm_parse_grid
    b'{"data":{"result":[{"values":[[1e300,"1"],[1700000000,"2"]]}]}}',
    b'{"data":{"result":[{"values":[[-1e300,"1"],[9.3e18,"2"]]}]}}',
    # subnormal / overflow / hex numbers through strtod
    b'{"data":{"result":[{"values":[[1e-320,"1e309"],[0x12,"0x1f"]]}]}}',
    # value string longer than the 63-byte strtod staging buffer
    b'{"data":{"result":[{"values":[[1700000000,"' + b"9" * 100 +
    b'"]]}]}}',
    # extra sample elements, empty strings, sample-shaped non-samples
    b'{"data":{"result":[{"values":[[1,2,3,4,[5,[6]],"x"],[7,""]]}]}}',
    # deep nesting far past kMaxDepth (stack-smash guard)
    b'[' * 100000,
    b'{"a":' * 50000,
    b'{"data":{"result":[{"values":' + b'[' * 2000 + b']' * 2000 +
    b'}]}}',
    # unterminated string / escape at EOF / bare unicode escape
    b'{"data":{"result":[{"values":[[1,"',
    b'{"data":"\\',
    b'{"data":"\\u00',
    # invalid UTF-8 and NUL bytes inside strings
    b'{"data":{"result":[{"values":[[1,"\xff\xfe\x00\x80"]]}]}}',
    # wavefront "data" key whose value is not a sample array
    b'{"timeseries":[{"data":{"data":[[1,2]]}}]}',
    b'{"timeseries":[{"data":[[1,2],{"data":[[3,4]]}]}]}',
    # duplicate timestamps en masse (merge/average path)
    b'{"data":{"result":[{"values":[' +
    b",".join(b'[1700000000,"%d"]' % i for i in range(500)) + b']}]}}',
    # UTF-8 BOM prefix (some proxies prepend it; scanner sees a non-JSON
    # lead byte and must reject cleanly)
    b'\xef\xbb\xbf{"data":{"result":[{"values":[[1,2]]}]}}',
    # huge/degenerate exponents inside STRING values (strtod staging)
    b'{"data":{"result":[{"values":[[1700000000,"1e99999"],'
    b'[1700000060,"-1e-99999"],[1700000120,"0x1.fp+1021"]]}]}}',
    # negative zero and exponent-only garbage
    b'{"data":{"result":[{"values":[[-0.0,"-0.0"],[1700000000,"e5"]]}]}}',
    # depth-limit straddle (kMaxDepth=64; every level incl. the innermost
    # scalar costs one value() frame): 62 objects + array + number = 64
    # frames -> deepest ACCEPTED body; 64 objects + number = 65 -> reject
    b'{"a":' * 62 + b'[1]' + b'}' * 62,
    b'{"a":' * 64 + b'1' + b'}' * 64,
    # target key nested inside a non-target structure and vice versa
    b'{"values":[[1,2]],"data":{"result":[{"values":[[3,"4"]]}]}}',
    b'{"data":{"result":[{"deep":{"values":[[5,"6"]]}}]}}',
]

_TOKENS = [b"nan", b"NaN", b"inf", b"-inf", b"1e309", b"1e-320", b"null",
           b"true", b"false", b"[[", b"]]", b"{}", b'""', b'"', b"\\u",
           b"\x00", b"\xff\xfe", b",,", b"::", b"-", b"0x", b"1e",
           b'"values":', b'"data":', b"[nan,1],"]


def gen_cases(seed: int, n: int):
    """Deterministic corpus: directed cases first, then seeded mutations."""
    yield from _DIRECTED
    rnd = random.Random(seed)
    for _ in range(max(0, n - len(_DIRECTED))):
        buf = bytearray(rnd.choice(_BASES))
        for _ in range(rnd.randint(1, 4)):
            op = rnd.randrange(6)
            if op == 0 and buf:  # truncate
                del buf[rnd.randrange(len(buf)):]
            elif op == 1 and buf:  # flip one byte
                i = rnd.randrange(len(buf))
                buf[i] = rnd.randrange(256)
            elif op == 2:  # insert a hostile token
                i = rnd.randrange(len(buf) + 1)
                buf[i:i] = rnd.choice(_TOKENS)
            elif op == 3 and buf:  # delete a slice
                i = rnd.randrange(len(buf))
                del buf[i:i + rnd.randrange(1, 16)]
            elif op == 4 and buf:  # duplicate a slice
                i = rnd.randrange(len(buf))
                j = min(len(buf), i + rnd.randrange(1, 32))
                buf[i:i] = buf[i:j]
            else:  # splice a random base fragment
                other = rnd.choice(_BASES)
                i = rnd.randrange(len(buf) + 1)
                j = rnd.randrange(len(other) + 1)
                buf[i:i] = other[:j]
        yield bytes(buf)


def _check_case(buf: bytes) -> None:
    for flavor in (native.FLAVOR_PROMETHEUS, native.FLAVOR_WAVEFRONT):
        parsed = native.parse_series(buf, flavor)
        if parsed is not None:
            ts, vals = parsed
            assert len(ts) == len(vals)
            ordered = ts[~np.isnan(ts)]
            if len(ordered) > 1:
                assert np.all(np.diff(ordered) >= 0), "ts not sorted"
        for max_steps in (512, 7):
            grid = native.parse_grid(buf, flavor, step=60,
                                     max_steps=max_steps)
            if grid is not None:
                gvals, gmask, start = grid
                assert len(gvals) == len(gmask)
                assert 1 <= len(gvals) <= max_steps
                assert gvals.dtype == np.float32
                assert gmask.dtype == bool


def _fuzz_resample(seed: int, n: int) -> None:
    rnd = random.Random(seed ^ 0x5EED)
    for case in range(n):
        m = rnd.randrange(0, 64)
        ts = np.array([rnd.choice([rnd.uniform(0, 2e9), float("nan"),
                                   float("inf"), -float("inf"), -1e300,
                                   1e300, 0.0])
                       for _ in range(m)])
        vals = np.array([rnd.uniform(-1e6, 1e6) for _ in range(m)])
        start = rnd.randrange(0, 2_000_000_000)
        end = start + rnd.choice([-600, 0, 60, 600, 86400])
        step = rnd.choice([1, 60, 3600])
        try:
            out = native.resample(ts, vals, start, end, step)
            if out is not None:
                ovals, omask = out
                assert len(ovals) == len(omask) == \
                    max(1, (end - start) // step)
        except Exception:
            # reported here, with THIS corpus's repro tuple — the parser
            # corpus's case index would misattribute the failure
            print(f"RESAMPLE-FAIL case={case} start={start} end={end} "
                  f"step={step} ts={ts.tolist()!r}", file=sys.stderr)
            raise


def _child_main(n_cases: int) -> int:
    # a child with NO native lib passes every case vacuously (each call
    # returns None) — that must be a loud failure, not silent green: the
    # ASAN leg in particular would otherwise report success with zero
    # sanitizer coverage when the instrumented build fails to compile/load
    if not native.available():
        print("FUZZ-FAIL native lib unavailable in child", file=sys.stderr)
        return 2
    override = os.environ.get("FOREMAST_NATIVE_SO")
    if override and native.lib_path() != override:
        print(f"FUZZ-FAIL loader ignored FOREMAST_NATIVE_SO "
              f"({native.lib_path()} != {override})", file=sys.stderr)
        return 2
    idx = -1
    try:
        for idx, buf in enumerate(gen_cases(SEED, n_cases)):
            _check_case(buf)
        _fuzz_resample(SEED, 500)
    except Exception as e:  # noqa: BLE001 — report the case, then fail
        print(f"FUZZ-FAIL case={idx} err={type(e).__name__}: {e} "
              f"buf[:160]={gen_case_repr(idx)}", file=sys.stderr)
        return 1
    print(f"fuzz ok: {idx + 1} parser cases + 500 resample cases")
    return 0


def gen_case_repr(idx: int) -> str:
    for i, buf in enumerate(gen_cases(SEED, idx + 1)):
        if i == idx:
            return repr(buf[:160])
    return "<regen failed>"


def _child_env(extra: dict | None = None) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra or {})
    return env


@pytest.mark.skipif(not native.available(), reason="native lib unavailable")
def test_fuzz_parsers_no_crash():
    """Seeded corpus in a subprocess: a segfault fails here, not pytest."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         str(N_CASES)],
        capture_output=True, text=True, timeout=600, env=_child_env(),
        cwd=REPO,
    )
    assert proc.returncode == 0, (
        f"fuzz child rc={proc.returncode}\nstdout={proc.stdout[-2000:]}\n"
        f"stderr={proc.stderr[-2000:]}")


def test_hostile_timestamp_bodies_degrade_not_crash(monkeypatch):
    """NaN/Infinity/1e300 timestamps must yield a sane Window on BOTH
    parse paths. json.loads accepts NaN/Infinity tokens (strict JSON does
    not), and the python span derivation used to raise on them
    (int(nan) -> ValueError) or build a window anchored at 1e300."""
    from foremast_tpu.dataplane import fetch

    bodies = [
        b'{"data":{"result":[{"values":[[NaN,2],[1700000000,"1"],'
        b'[NaN,3]]}]}}',
        b'{"data":{"result":[{"values":[[Infinity,2],'
        b'[1700000000,"1"]]}]}}',
        b'{"data":{"result":[{"values":[[-Infinity,2],[NaN,"3"]]}]}}',
        b'{"data":{"result":[{"values":[[1e300,"1"],'
        b'[1700000000,"2"]]}]}}',
    ]
    for forced_python in (False, True):
        if forced_python:
            monkeypatch.setattr(fetch.native, "parse_grid",
                                lambda *a, **k: None)
            monkeypatch.setattr(fetch.native, "parse_series",
                                lambda *a, **k: None)
        for body in bodies:
            w = fetch.window_from_prometheus_body(body)
            assert len(w.values) == len(w.mask) >= 1
            # span endpoints stay inside the shared cap (native kTsCap /
            # python TS_SPAN_CAP), never anchored at 1e300; the small
            # slack covers the +step / align rounding past the cap
            assert abs(w.start) <= fetch.TS_SPAN_CAP * 1.01, \
                (forced_python, body)


def _libasan_path() -> str | None:
    cxx = os.environ.get("CXX", "g++")
    try:
        out = subprocess.run([cxx, "-print-file-name=libasan.so"],
                             capture_output=True, text=True, timeout=30)
    except (OSError, subprocess.SubprocessError):
        return None
    path = out.stdout.strip()
    return path if path and os.path.sep in path and os.path.exists(path) \
        else None


@pytest.mark.skipif(not native.available(), reason="native lib unavailable")
def test_fuzz_parsers_asan(tmp_path):
    """Same corpus against an AddressSanitizer build: catches silent OOB
    reads. The child loads the ASAN .so via FOREMAST_NATIVE_SO (built on
    first use with FOREMAST_NATIVE_CXXFLAGS) under LD_PRELOADed libasan."""
    libasan = _libasan_path()
    if libasan is None:
        pytest.skip("libasan not present in toolchain")
    so = tmp_path / "foremast_native_asan.so"
    env = _child_env({
        "FOREMAST_NATIVE_SO": str(so),
        "FOREMAST_NATIVE_CXXFLAGS": "-fsanitize=address -g -O1",
        "LD_PRELOAD": libasan,
        # python itself leaks by design; abort_on_error turns real ASAN
        # reports into SIGABRT so the child's exit code flips
        "ASAN_OPTIONS": "detect_leaks=0:abort_on_error=1",
    })
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         str(N_CASES_ASAN)],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, (
        f"ASAN fuzz child rc={proc.returncode}\n"
        f"stdout={proc.stdout[-2000:]}\nstderr={proc.stderr[-3000:]}")


if __name__ == "__main__":
    if "--child" in sys.argv:
        n = int(sys.argv[sys.argv.index("--child") + 1])
        sys.exit(_child_main(n))
    sys.exit(_child_main(N_CASES))
