"""UBA's ranges cut to a graph of some hundred triples, for CPU runs."""
import json
from pathlib import Path

UBA = json.loads((Path(__file__).resolve().parents[1] / "configs"
                  / "lubm100-adhash.json").read_text())["cardinalities"]
TINY = dict(UBA, departments=[2, 3], fullprof=[1, 2], assocprof=[1, 2],
            asstprof=[1, 1], lecturer=[1, 1],
            undergraduates_per_faculty=[2, 3], graduates_per_faculty=[1, 2],
            research_groups=[1, 2],
            publications={"fullprof": [2, 3], "assocprof": [1, 2],
                          "asstprof": [1, 2], "lecturer": [0, 1],
                          "graduate": [0, 2]},
            graduates_per_teaching_assistant=[1, 2],
            graduates_per_research_assistant=[1, 2],
            undergraduates_per_advisor=2, degree_universities=6,
            research_interests=3)
