"""The versioned workload spec: one JSONL file = one scenario.

Line 1 is a header object (``kind``/``version``/``name``/``seed``/
``meta``); every following line is one request shape, sorted by
arrival offset. The spec deliberately records SHAPES, not content:
prompt text is synthesized deterministically at replay time
(:func:`build_prompt`) from the spec seed, the request index and the
prefix group, so a spec extracted from production traces carries no
user data — only the arrival process, the token-length mix, the
tenant mix and the prefix-sharing structure, which is exactly what
the serving plane's performance depends on (DistServe/Mooncake both
evaluate on replayed traces for this reason).

Determinism contract: the same spec file + the same replay seed
produce byte-identical prompts, so two replays (or a replay and a
capacity prediction) describe the same workload.
"""

from __future__ import annotations

import dataclasses
import json
import string
from typing import Dict, Iterable, List, Optional

SPEC_KIND = "pyspark_tf_gke_tpu.workload_spec"
SPEC_VERSION = 1

# power-of-2 token-length buckets for the shape histogram (shared by
# the round-trip test and the per-scenario summary); the last
# bucket is open-ended
_SHAPE_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048, 4096)


@dataclasses.dataclass
class SpecRequest:
    """One request shape.

    ``offset_s``: arrival time relative to the scenario start (the
    replay driver divides by its speed-up). ``prefix_group``: requests
    sharing a group share their first ``prefix_tokens`` prompt tokens
    — the radix-cache-relevant structure. ``deadline_ms``: the
    client's deadline, forwarded verbatim on replay (None = none)."""

    offset_s: float
    tenant: str = "default"
    prompt_tokens: int = 16
    output_tokens: int = 8
    prefix_group: Optional[str] = None
    prefix_tokens: int = 0
    deadline_ms: Optional[float] = None

    def to_dict(self) -> dict:
        d = {
            "offset_s": round(float(self.offset_s), 6),
            "tenant": self.tenant,
            "prompt_tokens": int(self.prompt_tokens),
            "output_tokens": int(self.output_tokens),
        }
        if self.prefix_group is not None:
            d["prefix_group"] = self.prefix_group
            d["prefix_tokens"] = int(self.prefix_tokens)
        if self.deadline_ms is not None:
            d["deadline_ms"] = round(float(self.deadline_ms), 3)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "SpecRequest":
        return cls(
            offset_s=float(d["offset_s"]),
            tenant=str(d.get("tenant", "default")),
            prompt_tokens=int(d["prompt_tokens"]),
            output_tokens=int(d["output_tokens"]),
            prefix_group=(str(d["prefix_group"])
                          if d.get("prefix_group") is not None else None),
            prefix_tokens=int(d.get("prefix_tokens", 0)),
            deadline_ms=(float(d["deadline_ms"])
                         if d.get("deadline_ms") is not None else None),
        )

    def validate(self, i: int) -> None:
        if self.offset_s < 0:
            raise ValueError(f"request {i}: offset_s must be >= 0")
        if self.prompt_tokens < 1:
            raise ValueError(f"request {i}: prompt_tokens must be >= 1")
        if self.output_tokens < 1:
            raise ValueError(f"request {i}: output_tokens must be >= 1")
        if self.prefix_group is not None and not (
                0 < self.prefix_tokens < self.prompt_tokens):
            raise ValueError(
                f"request {i}: prefix_tokens must be in "
                f"(0, prompt_tokens) when prefix_group is set "
                f"(got {self.prefix_tokens} of {self.prompt_tokens})")
        if self.deadline_ms is not None and self.deadline_ms <= 0:
            raise ValueError(f"request {i}: deadline_ms must be > 0")
        if not self.tenant:
            raise ValueError(f"request {i}: tenant must be non-empty")


@dataclasses.dataclass
class WorkloadSpec:
    """A named, seeded sequence of request shapes."""

    name: str
    requests: List[SpecRequest]
    seed: int = 0
    meta: Dict[str, object] = dataclasses.field(default_factory=dict)

    # -- invariants -------------------------------------------------------

    def validate(self) -> "WorkloadSpec":
        prev = 0.0
        for i, r in enumerate(self.requests):
            r.validate(i)
            if r.offset_s < prev:
                raise ValueError(
                    f"request {i}: offsets must be non-decreasing "
                    f"({r.offset_s} after {prev}) — save() sorts; a "
                    "hand-edited spec must stay sorted")
            prev = r.offset_s
        return self

    @property
    def duration_s(self) -> float:
        return self.requests[-1].offset_s if self.requests else 0.0

    @property
    def tenants(self) -> List[str]:
        return sorted({r.tenant for r in self.requests})

    # -- persistence ------------------------------------------------------

    def save(self, path: str) -> str:
        self.requests.sort(key=lambda r: r.offset_s)
        self.validate()
        header = {"kind": SPEC_KIND, "version": SPEC_VERSION,
                  "name": self.name, "seed": int(self.seed),
                  "meta": self.meta, "n_requests": len(self.requests)}
        with open(path, "w") as fh:
            fh.write(json.dumps(header, sort_keys=True) + "\n")
            for r in self.requests:
                fh.write(json.dumps(r.to_dict(), sort_keys=True) + "\n")
        return path

    @classmethod
    def load(cls, path: str) -> "WorkloadSpec":
        with open(path) as fh:
            lines = [ln for ln in fh.read().splitlines() if ln.strip()]
        if not lines:
            raise ValueError(f"{path}: empty spec file")
        header = json.loads(lines[0])
        if header.get("kind") != SPEC_KIND:
            raise ValueError(
                f"{path}: not a workload spec (kind="
                f"{header.get('kind')!r}; expected {SPEC_KIND!r})")
        if int(header.get("version", -1)) != SPEC_VERSION:
            raise ValueError(
                f"{path}: spec version {header.get('version')!r} not "
                f"supported (this build reads version {SPEC_VERSION})")
        spec = cls(name=str(header.get("name", "unnamed")),
                   seed=int(header.get("seed", 0)),
                   meta=dict(header.get("meta") or {}),
                   requests=[SpecRequest.from_dict(json.loads(ln))
                             for ln in lines[1:]])
        return spec.validate()

    # -- shape summary ----------------------------------------------------

    def shape_histogram(self) -> dict:
        """Bucketed shape summary — the round-trip equality oracle
        (traces → spec → replay must preserve it) and the compact
        per-scenario description a replay report carries."""

        def bucket(n: int) -> int:
            for b in _SHAPE_BUCKETS:
                if n <= b:
                    return b
            return _SHAPE_BUCKETS[-1] * 2  # open-ended overflow bucket

        prompt: Dict[int, int] = {}
        output: Dict[int, int] = {}
        tenants: Dict[str, int] = {}
        groups: Dict[str, int] = {}
        for r in self.requests:
            prompt[bucket(r.prompt_tokens)] = (
                prompt.get(bucket(r.prompt_tokens), 0) + 1)
            output[bucket(r.output_tokens)] = (
                output.get(bucket(r.output_tokens), 0) + 1)
            tenants[r.tenant] = tenants.get(r.tenant, 0) + 1
            if r.prefix_group is not None:
                groups[r.prefix_group] = groups.get(r.prefix_group, 0) + 1
        return {
            "n_requests": len(self.requests),
            "duration_s": round(self.duration_s, 3),
            "prompt_tokens": {str(k): v for k, v in sorted(prompt.items())},
            "output_tokens": {str(k): v for k, v in sorted(output.items())},
            "tenants": dict(sorted(tenants.items())),
            "prefix_groups": len(groups),
            "prefix_grouped_requests": sum(groups.values()),
        }


# -- deterministic prompt synthesis -------------------------------------------

# ASCII alphabet only: with the byte tokenizer 1 char == 1 token, so a
# prompt of N chars is EXACTLY N tokens — the spec's token counts land
# on the wire without a tokenizer round-trip. (HF-tokenized bundles
# replay too; the counts then approximate, which REPLAY.md documents.)
_ALPHABET = string.ascii_lowercase + string.digits + " "


def splitmix64_stream(key: str):
    """Deterministic uint64 stream derived from a string ``key``
    (FNV-1a seed + splitmix64 advance) — THE seeded-randomness
    primitive the replay AND chaos planes share: stable across Python
    versions and processes (``random.Random`` would also do, but one
    tiny explicit mixer documents that NOTHING environmental feeds
    any of them, and keeps the planes' determinism guarantees from
    diverging by copy drift)."""
    h = 1469598103934665603
    for c in key.encode():
        h = ((h ^ c) * 1099511628211) & 0xFFFFFFFFFFFFFFFF
    x = h or 1
    while True:
        x = (x + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
        z = x
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
        yield z ^ (z >> 31)


def seeded_unit_stream(key: str):
    """U[0,1) floats over :func:`splitmix64_stream` (53-bit draws)."""
    for z in splitmix64_stream(key):
        yield (z >> 11) / float(1 << 53)


def _chars(key: str, n: int) -> str:
    """``n`` deterministic alphabet chars for ``key`` (prompt
    synthesis; byte-identical to the pre-factoring inline mixer)."""
    stream = splitmix64_stream(key)
    return "".join(_ALPHABET[next(stream) % len(_ALPHABET)]
                   for _ in range(n))


def build_prompt(spec: WorkloadSpec, index: int) -> str:
    """The request's deterministic replay prompt: requests in the same
    prefix group share their first ``prefix_tokens`` chars exactly (so
    the radix cache sees real shared prefixes); the remainder is unique
    per request index. Same spec + same index ⇒ same prompt, every
    process, every run."""
    r = spec.requests[index]
    if r.prefix_group is not None and r.prefix_tokens > 0:
        head = _chars(f"{spec.seed}:{spec.name}:group:{r.prefix_group}",
                      r.prefix_tokens)
        tail = _chars(f"{spec.seed}:{spec.name}:req:{index}",
                      r.prompt_tokens - r.prefix_tokens)
        return head + tail
    return _chars(f"{spec.seed}:{spec.name}:req:{index}", r.prompt_tokens)


def spec_from_dicts(name: str, rows: Iterable[dict], *, seed: int = 0,
                    meta: Optional[dict] = None) -> WorkloadSpec:
    """Build + validate a spec from plain dict rows (the JSON-level
    schema) — the seam tools and tests share."""
    spec = WorkloadSpec(name=name, seed=seed, meta=dict(meta or {}),
                        requests=[SpecRequest.from_dict(r) for r in rows])
    spec.requests.sort(key=lambda r: r.offset_s)
    return spec.validate()
