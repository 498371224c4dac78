"""Back-to-back cold ``partition()`` calls.

They cycle through the traffic's ``point_sets`` point sets, made in
set-up from the seed, so no call repeats its predecessor's input. The
set-up call uses the first set; the window starts at the second.
"""
from chipbench.load import Load as _Load


class Load(_Load):
    def setup(self) -> None:
        sets = int(self.traffic["point_sets"])
        self.problems = [self.problem(self.points(1, i), None, 2, i)
                         for i in range(sets)]
        self.cold(self.problems[0])

    def call(self, i: int) -> int:
        prob = self.problems[(i + 1) % len(self.problems)]
        self.inputs.append((prob.points, None))
        self.results.append(self.cold(prob))
        return prob.n
