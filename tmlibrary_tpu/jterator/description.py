"""Pipeline and handle descriptions (YAML).

Reference parity: ``tmlib/workflow/jterator/description.py`` and
``project.py`` — ``PipelineDescription`` (the ``.pipe.yaml`` file: input
channels/objects, ordered module chain, output objects) and
``HandleDescriptions`` (one ``handles/*.handles.yaml`` per module instance).
The YAML schema keeps the reference's shape so existing pipeline projects
translate mechanically::

    # my.pipe.yaml
    description: Cell Painting segment+measure
    input:
      channels:
        - {name: DAPI, correct: true, align: false}
        - {name: Actin, correct: true, align: false}
        # a multiplexed plate: a stain of a later acquisition cycle,
        # read from that cycle under that cycle's shifts
        - {name: Mito, correct: true, align: true, cycle: 1}
    pipeline:
      - {handles: handles/smooth.handles.yaml, active: true}
      - {handles: handles/segment.handles.yaml, active: true}
    output:
      objects:
        - {name: nuclei, as_polygons: true}
"""

from __future__ import annotations

import dataclasses
from pathlib import Path

import yaml

from tmlibrary_tpu.errors import PipelineDescriptionError
from tmlibrary_tpu.jterator.handles import HandleCollection


@dataclasses.dataclass(frozen=True)
class ChannelInput:
    name: str
    correct: bool = True
    align: bool = False
    #: load the channel as a (Z, H, W) z-stack volume instead of one plane
    #: (feeds generate_volume_image / segment_volume; correction and
    #: alignment are per-plane concerns and are skipped for volumes)
    zstack: bool = False
    #: the acquisition cycle the channel's planes, illumination statistics
    #: and shifts are read from; None = the jterator step's ``cycle``
    #: argument.  It says where the pixels come from and nothing of what
    #: is computed: descriptions that differ in it share their programs
    cycle: int | None = None


@dataclasses.dataclass(frozen=True)
class ObjectInput:
    """A previously-segmented object type loaded from the store."""

    name: str


@dataclasses.dataclass(frozen=True)
class ObjectOutput:
    name: str
    as_polygons: bool = True


@dataclasses.dataclass
class PipelineDescription:
    """Parsed ``.pipe.yaml`` plus its resolved handle collections."""

    description: str
    channels: list[ChannelInput]
    objects_in: list[ObjectInput]
    modules: list[HandleCollection]
    objects_out: list[ObjectOutput]

    @classmethod
    def from_dict(cls, d: dict, base_dir: Path | None = None) -> "PipelineDescription":
        inp = d.get("input", {}) or {}
        channels = [
            ChannelInput(
                name=c["name"],
                correct=bool(c.get("correct", True)),
                align=bool(c.get("align", False)),
                zstack=bool(c.get("zstack", False)),
                cycle=cls._cycle(c),
            )
            for c in inp.get("channels", []) or []
        ]
        objects_in = [ObjectInput(name=o["name"]) for o in inp.get("objects", []) or []]
        modules: list[HandleCollection] = []
        for item in d.get("pipeline", []) or []:
            if not item.get("active", True):
                continue
            if "handles" in item and isinstance(item["handles"], str):
                if base_dir is None:
                    raise PipelineDescriptionError(
                        "handles given as a path but no base_dir provided"
                    )
                hpath = base_dir / item["handles"]
                if not hpath.exists():
                    raise PipelineDescriptionError(f"handles file missing: {hpath}")
                hd = yaml.safe_load(hpath.read_text())
            elif "handles" in item:
                hd = item["handles"]  # inline dict (convenient for tests)
            else:
                raise PipelineDescriptionError("pipeline item needs 'handles'")
            if not isinstance(hd, dict):
                raise PipelineDescriptionError(
                    f"handles for {item.get('source') or item.get('handles')!r}"
                    f" must be a mapping, got {type(hd).__name__}"
                    " (empty or malformed handles file?)"
                )
            # reference compat: upstream .pipe.yaml names the module via
            # ``source: [python/jtmodules/]<name>.py`` next to a handles
            # PATH, and upstream handles files carry no module name —
            # derive it from the source basename (tmlib/workflow/jterator/
            # description.py pairs source+handles the same way).  An
            # explicit ``module`` in the handles dict still wins.
            if "module" not in hd and item.get("source"):
                src = str(item["source"]).replace("\\", "/").rsplit("/", 1)[-1]
                stem, dot, ext = src.rpartition(".")
                if dot and ext.lower() in ("m", "r", "jl"):
                    raise PipelineDescriptionError(
                        f"non-Python module source '{item['source']}': "
                        "Matlab/R bridges are out of scope (SURVEY §8); "
                        "port the module to a registered JAX twin"
                    )
                hd = {**hd, "module": stem if dot else src}
            modules.append(HandleCollection.from_dict(hd))
        out = d.get("output", {}) or {}
        objects_out = [
            ObjectOutput(name=o["name"], as_polygons=bool(o.get("as_polygons", True)))
            for o in out.get("objects", []) or []
        ]
        if not modules:
            raise PipelineDescriptionError("pipeline has no active modules")
        return cls(
            description=d.get("description", ""),
            channels=channels,
            objects_in=objects_in,
            modules=modules,
            objects_out=objects_out,
        )

    @staticmethod
    def _cycle(channel: dict) -> int | None:
        cycle = channel.get("cycle")
        if cycle is None:
            return None
        if isinstance(cycle, bool) or not isinstance(cycle, int) or cycle < 0:
            raise PipelineDescriptionError(
                f"channel '{channel.get('name')}': cycle must be a "
                f"non-negative integer, got {cycle!r}"
            )
        return cycle

    @classmethod
    def load(cls, pipe_path: Path) -> "PipelineDescription":
        pipe_path = Path(pipe_path)
        d = yaml.safe_load(pipe_path.read_text())
        return cls.from_dict(d, base_dir=pipe_path.parent)

    def validate(self) -> None:
        """Check store-key dataflow: every module input key must be produced
        by an earlier module or be an input channel/object (the reference
        validates the same invariant when building a pipeline)."""
        available = {c.name for c in self.channels} | {o.name for o in self.objects_in}
        for mod in self.modules:
            for name, key in mod.array_inputs().items():
                if key not in available:
                    raise PipelineDescriptionError(
                        f"module '{mod.module}' input '{name}' reads key "
                        f"'{key}' which no upstream produces "
                        f"(available: {sorted(available)})"
                    )
            for h in mod.output:
                if h.key:
                    available.add(h.key)
                if h.type == "SegmentedObjects" and h.objects:
                    # downstream modules may read registered objects by name
                    available.add(h.objects)
        produced_objects = {
            h.objects
            for mod in self.modules
            for h in mod.output
            if h.type == "SegmentedObjects"
        }
        for obj in self.objects_out:
            if obj.name not in produced_objects:
                raise PipelineDescriptionError(
                    f"output objects '{obj.name}' never registered by any module"
                )


# alias matching the reference's class name for the per-module YAML
HandleDescriptions = HandleCollection
