"""Child processes under a deadline, the compile-cache rule, the device probe.

No jax import at module level: launchers that must stay off the chip
(the hunt parent, ``chip_smoke.py``, ``benchmarks/run.py``) import this
freely.

A TPU chip belongs to one process at a time, so a launcher asks a
short-lived child what devices there are (:func:`probe_devices`) and
never initialises a backend itself before its chip-bound children run.
Children run as Popen + poll + kill with output captured through a temp
file, never a PIPE: a chatty child would deadlock on the ~64KB pipe
buffer before exiting, and ``subprocess.run(timeout=...)`` waits on the
child after the timeout.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time
from typing import Optional, Sequence, Tuple


def _drain_fd(fd: int, pos: int) -> Tuple[bytes, int]:
    """Read everything past ``pos`` from a child's capture temp file.

    pread only: the child writes through a dup of this descriptor (one
    shared file offset), so a seek here would relocate the child's next
    write mid-file and corrupt the capture.
    """
    chunks = []
    while True:
        try:
            blk = os.pread(fd, 1 << 16, pos)
        except OSError:
            break
        if not blk:
            break
        chunks.append(blk)
        pos += len(blk)
    return b"".join(chunks), pos


def run_with_deadline(
    argv: Sequence[str],
    timeout_s: float,
    env: Optional[dict] = None,
    capture: bool = False,
    poll_s: float = 0.5,
    stream: bool = False,
) -> Tuple[Optional[int], str]:
    """Run ``argv``; return ``(returncode, output)``.

    ``returncode`` is None when the deadline hit and the child was killed
    (possibly unreapably — the non-blocking reap is best-effort). ``output``
    is combined stdout+stderr when ``capture`` or ``stream``, else "".

    ``stream=True`` additionally tees the child's output to this process's
    stdout *as it is produced* (each poll tick), so an outer observer that
    kills this process mid-run still sees everything the child printed so
    far — a buffered-until-exit capture shows nothing on such a kill.
    """
    import codecs

    out_f = tempfile.TemporaryFile() if (capture or stream) else None
    streamed = 0  # bytes already teed to stdout
    decoder = codecs.getincrementaldecoder("utf-8")("replace")
    try:
        proc = subprocess.Popen(
            argv, env=env,
            stdout=out_f if out_f is not None else subprocess.DEVNULL,
            stderr=subprocess.STDOUT if out_f is not None else subprocess.DEVNULL,
        )

        def _drain(pos: int) -> Tuple[bytes, int]:
            if out_f is None:
                return b"", pos
            return _drain_fd(out_f.fileno(), pos)

        def _tee() -> None:
            nonlocal streamed
            if not stream:
                return
            data, streamed = _drain(streamed)
            if data:
                # incremental decode: a multi-byte char split across ticks
                # must not become U+FFFD in the live tail
                sys.stdout.write(decoder.decode(data))
                sys.stdout.flush()

        deadline = time.time() + timeout_s
        rc: Optional[int] = None
        while time.time() < deadline:
            rc = proc.poll()
            _tee()
            if rc is not None:
                break
            time.sleep(poll_s)
        if rc is None:
            rc = proc.poll()  # the child may have exited during the last sleep
        if rc is None:
            proc.kill()
            try:  # bounded reap: never block the caller on a stuck child
                proc.wait(timeout=2.0)
            except subprocess.TimeoutExpired:
                pass
        _tee()  # flush whatever landed after the last tick (or the kill)
        output = ""
        if out_f is not None:
            data, _ = _drain(0)
            output = data.decode(errors="replace")
        return rc, output
    finally:
        if out_f is not None:
            out_f.close()


def last_json_object(text: str, prefix: str = "") -> Optional[dict]:
    """The last line of ``text`` that is ``prefix`` + one JSON object.

    How launchers read a child's result: stderr is merged into the
    capture and runtime teardown may chatter after the result line.
    """
    for line in reversed(text.splitlines()):
        if not line.startswith(prefix):
            continue
        try:
            doc = json.loads(line[len(prefix):])
        except ValueError:
            continue
        if isinstance(doc, dict):  # not a stray scalar line
            return doc
    return None


def run_many_with_deadline(
    jobs: Sequence[Tuple[str, Sequence[str], Optional[dict]]],
    timeout_s: float,
    poll_s: float = 0.5,
) -> dict:
    """Run labeled children concurrently under ONE shared deadline.

    ``jobs`` is ``[(label, argv, env), ...]``. Every child's combined
    stdout+stderr is teed to this process's stdout live, each complete line
    prefixed ``[label] `` — so an outer observer that kills this process
    still sees exactly which jobs were in flight and how far each got
    (same doctrine as ``run_with_deadline(stream=True)``, multiplexed).

    Returns ``{label: (returncode_or_None, full_output)}``; a ``None``
    returncode means the shared deadline hit and that child was killed.
    """
    import codecs

    class _Job:
        def __init__(self, label, argv, env):
            self.label = label
            self.out_f = tempfile.TemporaryFile()
            self.pos = 0  # bytes already drained
            self.pending = ""  # partial last line awaiting its newline
            self.decoder = codecs.getincrementaldecoder("utf-8")("replace")
            try:
                self.proc = subprocess.Popen(
                    argv, env=env, stdout=self.out_f, stderr=subprocess.STDOUT
                )
            except BaseException:
                self.out_f.close()
                raise
            self.rc: Optional[int] = None

        def drain(self, final: bool = False) -> None:
            data, self.pos = _drain_fd(self.out_f.fileno(), self.pos)
            text = self.pending + self.decoder.decode(data)
            *lines, self.pending = text.split("\n")
            for ln in lines:
                sys.stdout.write(f"[{self.label}] {ln}\n")
            if final and self.pending:
                sys.stdout.write(f"[{self.label}] {self.pending}\n")
                self.pending = ""
            sys.stdout.flush()

    js: list = []
    try:
        # inside the try: a Popen failure for a later job (fork EAGAIN is
        # plausible exactly when several jax interpreters start at once)
        # must not leak the already-started children unsupervised
        for (label, argv, env) in jobs:
            js.append(_Job(label, argv, env))
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            running = False
            for j in js:
                if j.rc is None:
                    j.rc = j.proc.poll()
                    j.drain()
                    running = running or j.rc is None
            if not running:
                break
            time.sleep(poll_s)
        for j in js:
            if j.rc is None:
                j.rc = j.proc.poll()
            if j.rc is None:
                j.proc.kill()
                try:  # non-blocking reap (see run_with_deadline)
                    j.proc.wait(timeout=2.0)
                except subprocess.TimeoutExpired:
                    pass
            j.drain(final=True)
        out = {}
        for j in js:
            data, _ = _drain_fd(j.out_f.fileno(), 0)
            out[j.label] = (j.rc, data.decode(errors="replace"))
        return out
    finally:
        for j in js:
            if j.proc.poll() is None:  # exception paths: no orphans
                j.proc.kill()
                try:
                    j.proc.wait(timeout=2.0)
                except subprocess.TimeoutExpired:
                    pass
            j.out_f.close()


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def xla_cache_dir(flag: Optional[str] = None) -> str:
    """The one compile-cache rule, for every process of the repo.

    ``JAX_COMPILATION_CACHE_DIR`` if the environment sets it; else
    ``flag`` (``hunt --jax-cache``); else ``<checkout>/.cache/xla``. The
    path is part of what makes a cache findable again, so it is never
    built from a temporary name, a pid or the time.
    """
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or (os.path.abspath(os.path.expanduser(flag)) if flag else "")
            or os.path.join(REPO_ROOT, ".cache", "xla"))


def use_xla_cache(flag: Optional[str] = None) -> str:
    """Apply :func:`xla_cache_dir` to this process and its children.

    Exported through ``os.environ`` (children inherit it, and jax binds
    it at import) and, where jax is already imported, through the live
    config. Everything else about the cache stays at jax's defaults
    (entries are written for compiles of a second or more).
    """
    cache = xla_cache_dir(flag)
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_compilation_cache_dir", cache)
    return cache


_PROBE_CODE = (
    "import json, jax, jaxlib\n"
    "from importlib import metadata\n"
    "d = jax.devices()\n"
    "try:\n"
    "    libtpu = metadata.version('libtpu')\n"
    "except metadata.PackageNotFoundError:\n"
    "    libtpu = None\n"
    "print('MTPU_DEVICES ' + json.dumps({'platform': d[0].platform,\n"
    "    'device_kind': d[0].device_kind, 'count': len(d),\n"
    "    'ids': [x.id for x in d], 'jax': jax.__version__,\n"
    "    'jaxlib': jaxlib.__version__, 'libtpu': libtpu}))\n"
)


def probe_devices(timeout_s: float = 120.0) -> dict:
    """Ask one short-lived child what devices a fresh process gets.

    For launchers that must not initialise a backend themselves. The
    child is pinned to the TPU unless ``JAX_PLATFORMS`` is already
    explicit (the CPU test harness), so an absent or busy chip raises
    with the runtime's own message instead of quietly counting CPU
    devices. It has exited — and released the chip — before this returns.

    Returns ``{"platform", "device_kind", "count", "ids", "jax",
    "jaxlib", "libtpu"}``.
    """
    env = dict(os.environ)
    if not env.get("JAX_PLATFORMS"):
        env["JAX_PLATFORMS"] = "tpu"
    rc, out = run_with_deadline(
        [sys.executable, "-c", _PROBE_CODE], timeout_s=timeout_s,
        env=env, capture=True, poll_s=0.1,
    )
    doc = last_json_object(out, "MTPU_DEVICES ") if rc == 0 else None
    if doc is None:
        raise RuntimeError(
            "device probe "
            + (f"timed out after {timeout_s:.0f}s" if rc is None
               else f"failed (rc={rc})")
            + f" with JAX_PLATFORMS={env['JAX_PLATFORMS']}: "
            + out[-1500:].strip()
        )
    return doc


def probe_tpu(timeout_s: float = 120.0) -> dict:
    """:func:`probe_devices` for launchers that measure the device:
    anything but a TPU raises ``RuntimeError("no TPU: ...")`` — there is
    no fallback to a CPU run under a device metric's name."""
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "tpu" not in platforms:  # no child needed to know
        raise RuntimeError(
            f"no TPU: JAX_PLATFORMS={platforms!r} rules it out")
    try:
        dev = probe_devices(timeout_s)
    except RuntimeError as exc:
        raise RuntimeError(f"no TPU: {exc}") from None
    if dev["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: jax found platform={dev['platform']} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    return dev


def kill_by_env_marker(marker: str) -> int:
    """SIGKILL every process whose environment carries ``marker``.

    Deep process trees here use ``start_new_session`` at several levels
    (executor trials, bench children), so neither killing a parent nor its
    process group reaches them — but they all inherit the launcher's env.
    Sweeping /proc by a unique marker reaps the whole tree, freeing the
    chip for whoever runs next. Used by benchmarks/run.py on config
    timeouts.
    """
    import signal as _signal

    me = os.getpid()
    killed = 0
    try:
        pids = os.listdir("/proc")
    except OSError:  # non-Linux host: nothing to sweep, don't sink the run
        return 0
    for pid_s in pids:
        if not pid_s.isdigit() or int(pid_s) == me:
            continue
        try:
            with open(f"/proc/{pid_s}/environ", "rb") as f:
                if marker.encode() not in f.read():
                    continue
            os.kill(int(pid_s), _signal.SIGKILL)
            killed += 1
        except (OSError, PermissionError):
            continue
    return killed


def run_swept(
    argv: Sequence[str],
    timeout_s: float,
    env: Optional[dict] = None,
    marker: Optional[str] = None,
    cwd: Optional[str] = None,
) -> Tuple[Optional[int], str, str]:
    """Run ``argv`` in its own session; on deadline, reap its WHOLE tree.

    The child gets a unique ``MTPU_SWEEP_MARKER`` in its env. If the
    deadline fires, the direct kill is followed by :func:`kill_by_env_marker`
    — descendants that ``start_new_session`` (executor trials, bench
    children) escape any killpg but inherit the env, and an orphan holding
    the chip fails everyone after us. Returns
    ``(rc_or_None, stdout, stderr)``; rc None = deadline.
    """
    env = dict(env if env is not None else os.environ)
    marker = marker or f"sweep-{os.getpid()}-{time.time_ns()}"
    # ACCUMULATE markers across nesting (an outer sweep → run.py → trials):
    # overwriting would strip the outer caller's marker from the whole
    # subtree, leaving its deadline sweep nothing to match. Matching is
    # substring-based, so a comma-joined list serves every level
    prev = env.get("MTPU_SWEEP_MARKER")
    env["MTPU_SWEEP_MARKER"] = f"{prev},{marker}" if prev else marker
    # temp files, never PIPE (module doctrine): an orphan that survives the
    # marker sweep keeps a pipe's write end open and communicate() would
    # discard everything the dead child DID print — exactly the
    # diagnostics this helper exists to preserve
    with tempfile.TemporaryFile() as out_f, tempfile.TemporaryFile() as err_f:
        proc = subprocess.Popen(
            list(argv), env=env, cwd=cwd,
            stdout=out_f, stderr=err_f, start_new_session=True,
        )
        try:
            rc: Optional[int] = proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            kill_by_env_marker(marker)
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                pass  # unreapable; the flushed temp files still read fine
            rc = None

        def _read(f) -> str:
            # pread, never seek: an orphan surviving the sweep still
            # shares the file description, and moving its offset would
            # let its next write corrupt the captured bytes
            data, _ = _drain_fd(f.fileno(), 0)
            return data.decode("utf-8", "replace")

        return rc, _read(out_f), _read(err_f)
