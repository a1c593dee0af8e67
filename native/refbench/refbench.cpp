// Honest CPU baseline harness: drives the REFERENCE ConsensusCore Arrow
// implementation (compiled unmodified from /root/reference, -O3 -msse3)
// on the workload pbccs_tpu.simulate.build_tasks draws, and reports
// ZMWs/sec.
//
// This is the "faithful reimplementation" clause of BASELINE.md satisfied
// with the original implementation itself: AddRead (FillAlphaBeta), the
// mutation-testing refinement loop, and the QV sweep are all reference code
// (reference ConsensusCore/src/C++/Arrow/SimpleRecursor.cpp:62-296,
// MultiReadMutationScorer.cpp:276-382, Consensus-inl.hpp:160-245).  Only
// this driver loop is ours: it re-states the ~60-line AbstractRefineConsensus
// control flow (greedy well-separated favorable mutations, template-hash
// cycle avoidance) because including Consensus.hpp would drag in the entire
// Quiver header chain, which needs much more of Boost than the shim set
// under stubs/ provides.
//
// Workload file (produced by dump_workload.py: the ZMWs a same-seed
// build_tasks call gives):
//   CONFIG <n_zmws> <tpl_len> <n_passes> <max_iterations> <min_zscore>
//   ZMW <id> <snrA> <snrC> <snrG> <snrT> <n_reads>
//   DRAFT <acgt-string>
//   READ <strand:0|1> <acgt-string>                       (x n_reads)
//   READWIN <strand:0|1> <tstart> <tend> <acgt-string>    (window variant:
//       per-read draft window, as the pipeline's POA extents produce;
//       used by tools/crossval_real.py for real-data cross-validation)

#include <ConsensusCore/Arrow/ArrowConfig.hpp>
#include <ConsensusCore/Checksum.hpp>
#include <ConsensusCore/Arrow/ContextParameters.hpp>
#include <ConsensusCore/Arrow/MultiReadMutationScorer.hpp>
#include <ConsensusCore/Arrow/MutationEnumerator.hpp>
#include <ConsensusCore/Features.hpp>
#include <ConsensusCore/Mutation.hpp>
#include <ConsensusCore/Read.hpp>

#include <algorithm>
#include <chrono>
#include <cmath>
#include <cstdio>
#include <fstream>
#include <functional>
#include <iostream>
#include <set>
#include <sstream>
#include <string>
#include <vector>

using namespace ConsensusCore;
using namespace ConsensusCore::Arrow;

// Checksum.cpp needs boost/crc.hpp (not in the shim set); the symbols are
// only reachable from Read::ToString diagnostics, never on the bench path.
namespace ConsensusCore {
std::string Checksum::Of(const QvSequenceFeatures&) { return "na"; }
std::string Checksum::Of(const ArrowSequenceFeatures&) { return "na"; }
}  // namespace ConsensusCore

namespace {

struct ReadInput {
    int strand = 0;
    int tStart = -1, tEnd = -1;  // -1: full draft span (legacy READ lines)
    std::string seq;
};

struct ZmwInput {
    std::string id;
    double snr[4];
    std::string draft;
    std::vector<ReadInput> reads;
};

struct Workload {
    int nZmws = 0, tplLen = 0, nPasses = 0, maxIterations = 10;
    double minZScore = -5.0;
    std::vector<ZmwInput> zmws;
};

Workload LoadWorkload(const std::string& path)
{
    std::ifstream in(path);
    if (!in) { std::cerr << "cannot open " << path << "\n"; exit(1); }
    Workload w;
    std::string tag;
    while (in >> tag) {
        if (tag == "CONFIG") {
            in >> w.nZmws >> w.tplLen >> w.nPasses >> w.maxIterations >> w.minZScore;
        } else if (tag == "ZMW") {
            ZmwInput z;
            int nReads;
            in >> z.id >> z.snr[0] >> z.snr[1] >> z.snr[2] >> z.snr[3] >> nReads;
            std::string t;
            in >> t >> z.draft;                        // DRAFT <seq>
            for (int r = 0; r < nReads; ++r) {
                ReadInput ri;
                in >> t;
                if (t == "READWIN")                    // READWIN <strand> <ts> <te> <seq>
                    in >> ri.strand >> ri.tStart >> ri.tEnd >> ri.seq;
                else                                   // READ <strand> <seq>
                    in >> ri.strand >> ri.seq;
                z.reads.push_back(std::move(ri));
            }
            w.zmws.push_back(std::move(z));
        }
    }
    return w;
}

// Same semantics as the reference's BestSubset (Consensus-inl.hpp:99-119):
// repeatedly take the max-scoring mutation and drop everything whose start
// lies within +/- separation (inclusive) of its start.
std::vector<ScoredMutation> GreedyWellSeparated(std::vector<ScoredMutation> cand,
                                                int separation)
{
    std::vector<ScoredMutation> out;
    while (!cand.empty()) {
        auto bestIt = std::max_element(
            cand.begin(), cand.end(),
            [](const ScoredMutation& a, const ScoredMutation& b) {
                return a.Score() < b.Score();
            });
        ScoredMutation best = *bestIt;
        out.push_back(best);
        std::vector<ScoredMutation> keep;
        for (const auto& s : cand)
            if (s.Start() < best.Start() - separation ||
                s.Start() > best.Start() + separation)
                keep.push_back(s);
        cand.swap(keep);
    }
    return out;
}

std::vector<Mutation> AsMutations(const std::vector<ScoredMutation>& s)
{
    return std::vector<Mutation>(s.begin(), s.end());
}

// The reference refinement control flow (AbstractRefineConsensus,
// Consensus-inl.hpp:160-245): round 0 tests every unique single-base
// mutation, later rounds only the neighborhood of the previous round's
// favorables; apply the best well-separated subset, trimming to one
// mutation when the would-be template was already visited.
bool Refine(ArrowMultiReadMutationScorer& mms, int maxIterations,
            size_t* nTested, size_t* nApplied)
{
    const int kSeparation = 10, kNeighborhood = 20;
    std::hash<std::string> hasher;
    std::set<size_t> tplHistory;
    std::vector<ScoredMutation> favorables;

    for (int iter = 0; iter < maxIterations; ++iter) {
        UniqueSingleBaseMutationEnumerator enumerator(mms.Template());
        std::vector<Mutation> toTry =
            (iter == 0) ? enumerator.Mutations()
                        : UniqueNearbyMutations(enumerator, AsMutations(favorables),
                                                kNeighborhood);
        *nTested += toTry.size();
        favorables.clear();
        for (const Mutation& m : toTry) {
            if (mms.FastIsFavorable(m)) {
                double s = mms.Score(m);
                favorables.push_back(m.WithScore(static_cast<float>(s)));
            }
        }
        if (favorables.empty()) return true;

        std::vector<ScoredMutation> best = GreedyWellSeparated(favorables, kSeparation);
        if (best.size() > 1) {
            std::string nextTpl = ApplyMutations(AsMutations(best), mms.Template());
            if (tplHistory.count(hasher(nextTpl)))
                best.resize(1);
        }
        *nApplied += best.size();
        tplHistory.insert(hasher(mms.Template()));
        mms.ApplyMutations(AsMutations(best));
    }
    return false;
}

// ConsensusQVs (Consensus-inl.hpp:277-297).
std::vector<int> QvSweep(ArrowMultiReadMutationScorer& mms)
{
    std::vector<int> qvs;
    UniqueSingleBaseMutationEnumerator enumerator(mms.Template());
    const size_t L = mms.Template().length();
    for (size_t pos = 0; pos < L; ++pos) {
        double scoreSum = 0.0;
        for (const Mutation& m : enumerator.Mutations(static_cast<int>(pos),
                                                      static_cast<int>(pos) + 1)) {
            double s = mms.Score(m);
            if (s < 0.0) scoreSum += std::exp(s);
        }
        double p = 1.0 - 1.0 / (1.0 + scoreSum);
        if (p <= 0.0) p = std::numeric_limits<double>::min();
        qvs.push_back(static_cast<int>(std::round(-10.0 * std::log10(p))));
    }
    return qvs;
}

}  // namespace

int main(int argc, char** argv)
{
    if (argc < 2) {
        std::cerr << "usage: refbench WORKLOAD [--repeats N]\n";
        return 1;
    }
    int repeats = 1;
    std::string dumpPath;
    for (int i = 2; i + 1 < argc; ++i) {
        if (std::string(argv[i]) == "--repeats") repeats = std::atoi(argv[i + 1]);
        if (std::string(argv[i]) == "--dump") dumpPath = argv[i + 1];
    }

    Workload w = LoadWorkload(argv[1]);
    std::cerr << "refbench: Z=" << w.zmws.size() << " L=" << w.tplLen
              << " P=" << w.nPasses << " iters=" << w.maxIterations
              << " minZ=" << w.minZScore << "\n";

    std::vector<double> repSecs;
    size_t nTested = 0, nApplied = 0, nConverged = 0, nDroppedReads = 0;
    double qvSum = 0.0; size_t qvCount = 0;

    std::ofstream dump;
    if (!dumpPath.empty()) dump.open(dumpPath);

    for (int rep = 0; rep < repeats; ++rep) {
        nTested = nApplied = nConverged = nDroppedReads = 0;
        qvSum = 0.0; qvCount = 0;
        auto t0 = std::chrono::steady_clock::now();
        for (const ZmwInput& z : w.zmws) {
            ContextParameters ctx(SNR(z.snr[0], z.snr[1], z.snr[2], z.snr[3]));
            ArrowConfig config(ctx, ConsensusCore::Arrow::BandingOptions(12.5));
            ArrowMultiReadMutationScorer mms(config, z.draft);
            for (const auto& sr : z.reads) {
                ArrowSequenceFeatures features(sr.seq);
                int ts = sr.tStart >= 0 ? sr.tStart : 0;
                int te = sr.tEnd >= 0 ? sr.tEnd
                                      : static_cast<int>(z.draft.size());
                MappedArrowRead mr(ArrowRead(features, z.id, "N/A"),
                                   sr.strand ? REVERSE_STRAND : FORWARD_STRAND,
                                   ts, te);
                if (mms.AddRead(mr, w.minZScore) != SUCCESS) ++nDroppedReads;
            }
            if (Refine(mms, w.maxIterations, &nTested, &nApplied)) ++nConverged;
            std::vector<int> qvs = QvSweep(mms);
            for (int qv : qvs) { qvSum += qv; ++qvCount; }
            if (rep == 0 && dump.is_open()) {
                std::string qstr;  // phred+33, clamped like QVsToASCII
                for (int qv : qvs)
                    qstr += static_cast<char>(std::min(std::max(qv, 0), 93) + 33);
                dump << z.id << " " << mms.Template() << " " << qstr << "\n";
            }
        }
        auto t1 = std::chrono::steady_clock::now();
        repSecs.push_back(std::chrono::duration<double>(t1 - t0).count());
    }

    // median run time over the repetitions
    std::sort(repSecs.begin(), repSecs.end());
    double medSec = repSecs[repSecs.size() / 2];
    double zps = w.zmws.size() / medSec;
    std::printf("{\"reference_cpp_zmws_per_sec\": %.6f, \"bench_s\": %.4f, "
                "\"n_zmws\": %zu, \"converged\": %zu, \"dropped_reads\": %zu, "
                "\"mutations_tested\": %zu, \"mutations_applied\": %zu, "
                "\"mean_qv\": %.3f, \"threads\": 1}\n",
                zps, medSec, w.zmws.size(), nConverged, nDroppedReads,
                nTested, nApplied, qvCount ? qvSum / qvCount : 0.0);
    return 0;
}
