"""Shared build / load / registration plumbing for the native CPU
kernels (native/*.cc).

Three modules used to duplicate the same on-demand toolchain dance —
stale-check the .so against the source, g++ into native/build/ under a
per-process temp name, ctypes-load, optionally register XLA FFI custom
calls (histogram_native.py, binning_native.py, native_csv.py). This
helper centralizes it:

  * one compile recipe (g++ -O3 -std=c++17 -shared -fPIC [+extra flags],
    with jax.ffi's bundled XLA FFI headers when the kernel needs them);
  * one failure policy: any build/load/registration error degrades to
    `available() == False` so the package works without a toolchain,
    but emits a ONE-TIME RuntimeWarning naming the kernel and the
    exception — a silent fallback to a ~5x slower impl must never be an
    invisible perf regression (ADVICE r5);
  * one thread-safe "once per process" state machine per library.

FFI registration is lazy and optional: ctypes-only callers (e.g. the
NumPy binning fast path) never import jax.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
import warnings
from typing import Dict, Optional, Sequence

from ydf_tpu.utils import failpoints

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
BUILD_DIR = os.path.join(NATIVE_DIR, "build")

# Optional sanitizer builds for ALL native kernels — correctness tooling
# for every native PR (tests/test_native_sanitize.py drives the kernels
# under it in a subprocess). Resolved EAGERLY per build/load so a typo
# fails at the env boundary, not as a silent normal build.
_SANITIZE_MODES = {"asan": ("-fsanitize=address",),
                   "ubsan": ("-fsanitize=undefined",
                             "-fno-sanitize-recover=undefined"),
                   # ThreadSanitizer: the work-stealing pool's claim /
                   # steal / completion protocol runs under it in
                   # tests/test_native_sanitize.py (steal-heavy stall
                   # schedule included).
                   "tsan": ("-fsanitize=thread",)}


def sanitize_mode():
    """YDF_TPU_NATIVE_SANITIZE ∈ {asan, ubsan, tsan} selects a sanitizer
    build
    (separate .so name, so it never clobbers — or staleness-races — the
    normal build); empty/unset means the plain -O3 build."""
    env = os.environ.get("YDF_TPU_NATIVE_SANITIZE", "").strip().lower()
    if env in ("", "0", "off", "none"):
        return None
    if env not in _SANITIZE_MODES:
        raise ValueError(
            f"YDF_TPU_NATIVE_SANITIZE={env!r} is not a sanitizer mode; "
            f"expected one of {sorted(_SANITIZE_MODES)} (or unset)"
        )
    return env


class NativeLibrary:
    """One native shared library: built on first use, loaded once,
    optionally registered as XLA FFI custom-call targets.

    Args:
      src_name: source file name(s) under native/ — a single name or a
        sequence compiled together into one .so (e.g. the histogram and
        binning kernels share a library so they share the persistent
        thread pool in native/thread_pool.h).
      lib_name: output .so name under native/build/.
      ffi_targets: XLA custom-call target name -> exported handler
        symbol; registered (platform "cpu") on the first
        `ensure_ffi_registered()` call.
      extra_cflags: appended to the compile command (e.g. "-pthread").
      needs_ffi_headers: add -I jax.ffi.include_dir() (requires jax at
        BUILD time only; pre-built libraries load without it).
      extra_deps: additional files under native/ (headers) whose mtime
        participates in the staleness check.
    """

    def __init__(
        self,
        src_name,
        lib_name: str,
        ffi_targets: Optional[Dict[str, str]] = None,
        extra_cflags: Sequence[str] = (),
        needs_ffi_headers: bool = True,
        extra_deps: Sequence[str] = (),
    ):
        names = (
            (src_name,) if isinstance(src_name, str) else tuple(src_name)
        )
        self.srcs = tuple(os.path.join(NATIVE_DIR, s) for s in names)
        self.src = self.srcs[0]  # primary source, used in warnings
        self.deps = tuple(os.path.join(NATIVE_DIR, d) for d in extra_deps)
        # Sanitizer builds get their own .so name: a -fsanitize build
        # must never overwrite the normal library (or constantly re-mark
        # it stale for tier-1); resolved once at library-object creation,
        # i.e. set YDF_TPU_NATIVE_SANITIZE before the first ydf_tpu
        # import of the process (the sanitize test uses a subprocess).
        self.sanitize = sanitize_mode()
        if self.sanitize:
            base, ext = os.path.splitext(lib_name)
            lib_name = f"{base}.{self.sanitize}{ext}"
        self.lib_path = os.path.join(BUILD_DIR, lib_name)
        self.ffi_targets = dict(ffi_targets or {})
        self.extra_cflags = tuple(extra_cflags)
        self.needs_ffi_headers = needs_ffi_headers
        self._lock = threading.Lock()
        self._lib: Optional[ctypes.CDLL] = None
        self._failed = False
        self._ffi_registered = False
        self._warned = False

    # ------------------------------------------------------------------ #

    def _warn_once(self, stage: str, err: BaseException) -> None:
        if self._warned:
            return
        self._warned = True
        warnings.warn(
            f"ydf_tpu native kernel {os.path.basename(self.src)!r} "
            f"unavailable ({stage}: {type(err).__name__}: {err}); falling "
            f"back to the pure-Python/XLA path. This can be a large perf "
            f"regression — install a C++ toolchain or set the relevant "
            f"impl override to silence this warning.",
            RuntimeWarning,
            stacklevel=3,
        )

    def is_stale(self) -> bool:
        """True when the built .so is missing or older than any source
        or dependency header (the tier-1 native smoke check asserts the
        opposite after a load)."""
        if not os.path.isfile(self.lib_path):
            return True
        lib_mtime = os.path.getmtime(self.lib_path)
        return any(
            os.path.isfile(p) and lib_mtime < os.path.getmtime(p)
            for p in self.srcs + self.deps
        )

    def _build_if_needed(self) -> None:
        missing = [p for p in self.srcs if not os.path.isfile(p)]
        if os.path.isfile(self.lib_path) and not self.is_stale():
            return
        if missing:
            raise FileNotFoundError(missing[0])
        failpoints.hit("native.build")
        cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC"]
        if self.sanitize:
            cmd += list(_SANITIZE_MODES[self.sanitize])
            cmd += ["-g", "-fno-omit-frame-pointer"]
        cmd += list(self.extra_cflags)
        cmd += ["-I", NATIVE_DIR]
        if self.needs_ffi_headers:
            import jax

            cmd += ["-I", jax.ffi.include_dir()]
        os.makedirs(BUILD_DIR, exist_ok=True)
        # Per-process temp name: concurrent cold builds must not
        # os.replace each other's half-written objects.
        tmp = f"{self.lib_path}.{os.getpid()}.tmp"
        cmd += list(self.srcs) + ["-o", tmp]
        subprocess.run(cmd, check=True, capture_output=True, timeout=180)
        os.replace(tmp, self.lib_path)

    def load(self) -> Optional[ctypes.CDLL]:
        """Builds (if needed) and ctypes-loads the library once per
        process; None after any failure (warned once)."""
        if self._lib is not None:
            return self._lib
        if self._failed:
            return None
        with self._lock:
            if self._lib is not None or self._failed:
                return self._lib
            try:
                self._build_if_needed()
                self._lib = ctypes.CDLL(self.lib_path)
            except failpoints.FailpointError as e:
                # Injected fault: TRANSIENT by contract (failpoints fire
                # once) — warn and fall back for this call, but do not
                # latch _failed: the retry path is the point.
                self._warn_once("build/load (injected)", e)
            except Exception as e:
                self._failed = True
                self._warn_once("build/load", e)
            return self._lib

    def available(self) -> bool:
        return self.load() is not None

    def ensure_ffi_registered(self) -> bool:
        """Registers every ffi_target with jax.ffi (CPU platform), once.
        Returns availability of the registered library."""
        if self._ffi_registered:
            return True
        if self._failed:
            return False
        lib = self.load()
        if lib is None:
            return False
        with self._lock:
            if self._ffi_registered:
                return True
            try:
                failpoints.hit("native.register")
                import jax

                for target, symbol in self.ffi_targets.items():
                    jax.ffi.register_ffi_target(
                        target,
                        jax.ffi.pycapsule(getattr(lib, symbol)),
                        platform="cpu",
                    )
                self._ffi_registered = True
            except failpoints.FailpointError as e:
                # Injected registration fault is transient: callers see
                # one unavailable() (→ XLA fallback, bit-identical) and
                # the NEXT ensure_ffi_registered() retries and succeeds
                # — the recovery the chaos suite asserts.
                self._warn_once("ffi registration (injected)", e)
            except Exception as e:
                self._failed = True
                self._warn_once("ffi registration", e)
            return self._ffi_registered


# The training kernels (histogram f32 + int8-quantized, binning, and
# the row-routing/prediction-update family) are compiled TOGETHER into
# one shared library so they share the lazily created persistent worker
# pool in native/thread_pool.h (per-call std::thread spawn/join was a
# measurable fixed cost at the boosting loop's call rate — ROADMAP open
# item). The pool's lifetime is this loaded module's; YDF_TPU_HIST_THREADS
# sizes it at first use, and the per-call env resolutions
# (YDF_TPU_HIST_THREADS / YDF_TPU_BIN_THREADS / YDF_TPU_ROUTE_THREADS)
# still bound each call's task wave.
KERNELS_LIB = NativeLibrary(
    src_name=(
        "histogram_ffi.cc", "binning_ffi.cc", "routing_ffi.cc",
        "serving_ffi.cc",
    ),
    lib_name="libydfkernels.so",
    ffi_targets={
        "ydf_histogram": "YdfHistogram",
        "ydf_histogram_q8": "YdfHistogramQ8",
        "ydf_histogram_routed": "YdfHistogramRouted",
        "ydf_histogram_q8_routed": "YdfHistogramQ8Routed",
        "ydf_binning": "YdfBinning",
        "ydf_route_update": "YdfRouteUpdate",
        "ydf_leaf_update": "YdfLeafUpdate",
        "ydf_leaf_update_grad": "YdfLeafUpdateGrad",
        "ydf_route_tree": "YdfRouteTree",
        # Batched data-bank serving (native/serving_ffi.cc): the FFI
        # surface of the production serving engine; the ctypes handle
        # surface (serving/native_serve.py) rides the same .so.
        "ydf_serve_batch": "YdfServeBatch",
    },
    extra_cflags=("-pthread",),
    extra_deps=("thread_pool.h", "route_simd.h"),
)
